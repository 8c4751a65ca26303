"""The one traffic generator: every mix is a data file under
``bench/traffic/`` and names its ``kind`` here.

* ``mooncake``: simulator traces with the Mooncake statistics of
  ``repro.traces.mooncake`` (copied from commit 900a6150 so the yardstick
  does not move with ``src/``): two-state MMPP arrivals compressed to the
  target rate, a log-normal input-length mixture clipped to the profile,
  log-normal outputs, Zipf-drawn shared prefixes.

Every seed gets the same work: a mooncake window replays the same
sequence of base traces, trace ``k`` drawn from the file's ``base_seed``
plus ``k``.  The run's seed reaches only the simulator's own generator
(ECMP hashing, tie-breaks), never the requests.  The mix holds what is
shaped by traffic (lengths, sharing, load, the capacity model's
constants); the deployment's own numbers (instances, ``beta_max``, fabric
egress) come from the configuration.
"""

from __future__ import annotations

import dataclasses

import numpy as np

B_TOK = 16          # tokens per KV block (the paper's prefix-match unit)


def n_blocks(tokens: int) -> int:
    return (tokens + B_TOK - 1) // B_TOK


def small_seed(seed: int, *stream: int) -> int:
    """A 31-bit seed for program options that want a small integer."""
    return int(np.random.SeedSequence([int(seed), *stream]).generate_state(1)[0]
               >> 1)


# ------------------------------------------------------------ mooncake
@dataclasses.dataclass
class SimRequest:
    """Field for field what ``repro.sim`` reads from a trace request."""
    request_id: int
    arrival: float
    input_len: int
    output_len: int
    block_hashes: tuple
    share_group: int
    slo: float


def _input_lengths(rng, n, prof):
    body = rng.lognormal(mean=np.log(2600.0), sigma=1.0, size=n)
    tail = rng.lognormal(mean=np.log(14000.0), sigma=0.7, size=n)
    pick_tail = rng.random(n) < 0.25
    lens = np.where(pick_tail, tail, body)
    return np.clip(lens, prof["min_input"], prof["max_input"]).astype(np.int64)


def _mmpp_arrivals(rng, n, base_rate, burst_factor=4.0, dwell_calm=1.2,
                   dwell_burst=0.35):
    times = np.empty(n)
    t, state = 0.0, 0
    state_end = rng.exponential(dwell_calm)
    for i in range(n):
        rate = base_rate * (burst_factor if state == 1 else 1.0)
        t += rng.exponential(1.0 / rate)
        while t > state_end:
            state = 1 - state
            state_end = t + rng.exponential(dwell_burst if state == 1 else dwell_calm)
        times[i] = t
    return times


def mooncake_trace(prof: dict, *, duration: float, target_rps: float,
                   seed: int, n_share_groups: int = 48,
                   zipf_a: float = 1.4) -> list[SimRequest]:
    """``generate_trace`` of ``repro/traces/mooncake.py`` at 900a6150."""
    rng = np.random.default_rng(seed)
    n = max(int(duration * target_rps * 1.3) + 8, 8)
    raw = _mmpp_arrivals(rng, n, base_rate=max(target_rps, 1e-6) / 1.9)
    span = raw[-1] - raw[0]
    want_n = max(int(duration * target_rps), 1)
    arrivals = (raw - raw[0]) * (duration / span) * (n / max(want_n, 1))
    arrivals = arrivals[arrivals < duration][:want_n * 2]
    m = len(arrivals)
    in_lens = _input_lengths(rng, m, prof)
    out_lens = np.clip(rng.lognormal(np.log(prof["out_median"]),
                                     prof["out_sigma"], size=m),
                       1, 2048).astype(np.int64)
    group_prefix_blocks = rng.integers(
        low=max(2, prof["min_input"] // (2 * B_TOK)),
        high=max(3, prof["max_input"] // (2 * B_TOK)), size=n_share_groups)
    reqs = []
    for i in range(m):
        l_in = int(in_lens[i])
        blocks = n_blocks(l_in)
        if rng.random() < prof["p_share"]:
            g = int(min(rng.zipf(zipf_a), n_share_groups) - 1)
            pb = int(min(group_prefix_blocks[g], max(blocks - 1, 1)))
            hashes = tuple(("g", g, j) for j in range(pb)) + tuple(
                ("r", i, j) for j in range(blocks - pb))
        else:
            g = -1
            hashes = tuple(("r", i, j) for j in range(blocks))
        reqs.append(SimRequest(i, float(arrivals[i]), l_in, int(out_lens[i]),
                               hashes, g, prof["slo"]))
    return reqs


def mooncake_capacity(prof: dict, *, n_prefill: int, n_decode: int,
                      beta_max: int, fabric_bytes,
                      tor_egress_bytes_per_s: float,
                      agg_egress_bytes_per_s: float, tier3_frac: float,
                      background: float, headroom: float, iter_ab,
                      prefill_cd, seed: int = 0, n: int = 4000) -> float:
    """``profile_capacity`` of ``repro/traces/mooncake.py`` at 900a6150,
    with the iteration and prefill models passed in.  The network term
    divides by the bytes of a mean request, ``fabric_bytes(mean input,
    mean hit fraction)``, the configuration's reference."""
    rng = np.random.default_rng(seed)
    mi = float(_input_lengths(rng, n, prof).mean())
    mo = float(np.clip(rng.lognormal(np.log(prof["out_median"]),
                                     prof["out_sigma"], size=n), 1, 2048).mean())
    fabric = min(tor_egress_bytes_per_s,
                 agg_egress_bytes_per_s / max(tier3_frac, 1e-6))
    fabric *= 1.0 - background
    a, b = iter_ab
    c, d = prefill_cd
    prefill_rps = n_prefill / (c * mi + d)
    decode_rps = n_decode * beta_max / (mo * (a + b * beta_max))
    mean_eff = fabric_bytes(mi, prof["p_share"] * 0.55)
    net_rps = fabric * headroom / max(mean_eff, 1.0)
    return min(prefill_rps, decode_rps, net_rps)


class MooncakeWindow:
    """Simulations for a window: trace ``k`` is base trace ``k`` of the mix,
    generated when the window first asks for it.  ``deployment`` holds the
    configuration's side of the capacity model: ``n_prefill``,
    ``n_decode``, ``beta_max``, ``fabric_bytes``, the fabric egress and
    the iteration and prefill models."""

    def __init__(self, mix: dict, deployment: dict):
        self.mix = mix
        self.capacity_rps = mooncake_capacity(mix["profile"], **deployment,
                                              **mix["capacity_model"])
        self.rps = mix["load"] * self.capacity_rps
        self._traces: dict[int, list[SimRequest]] = {}

    def trace(self, k: int) -> list[SimRequest]:
        if k not in self._traces:
            self._traces[k] = mooncake_trace(
                self.mix["profile"], duration=self.mix["duration"],
                target_rps=self.rps, seed=self.mix["base_seed"] + k)
        return self._traces[k]
