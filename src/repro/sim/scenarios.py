"""ScenarioPlane: fleet-scale what-if sweeps as one batched JAX program.

The event-loop simulator answers one scenario at a time at Python speed;
every beyond-paper study on the ROADMAP (optimality gaps at scale, chunk ×
NIC-policy × rewire grids, autoscaling policies) needs *thousands* of
scenarios.  This module fuses the two NumPy fixed-point hot loops the
planes already isolated —

* ``FlowPlane._recompute_rates``  -> ``kernels.waterfill`` (jitted
  ``lax.while_loop`` + optional Pallas inner reduction, bit-exact under
  f64, proven by ``tests/test_scenarioplane.py``);
* ``InstancePlane._step_rows_vector``'s token/finish/KV-growth array ops
  -> :func:`cohort_step` (jitted, bit-exact in ``exact_clamp`` mode);

— into a fixed-timestep fluid scenario model and ``vmap``s a leading
*scenario axis* over it: seeds × scheduler × chunk size × NIC policy ×
rewire schedules run as **one** jitted device program
(:meth:`ScenarioPlane.sweep`), returning per-scenario TTFT/TBT/SLO summary
arrays.

Modelling contract: the two ported solvers are bit-exact against their
NumPy planes; the surrounding scenario engine is a *fluid* (dt-stepped)
approximation of the event loop — same cost model (Eqs. (2)-(7)), same
max-min network, same continuous-batching iteration clock, but scheduling
decisions quantise to ``dt`` and the radix cache is not modelled
(``s_eff = s_r``).  It ranks policies; the event loop remains the ground
truth for absolute paper numbers.  Batched row ``i`` is bit-identical to a
solo run of scenario ``i`` at the same padding (the vmap-consistency
test): every loop body is a no-op for converged/padded lanes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from repro.core.cost import (
    H100_TP4_ITER, H100_TP4_PREFILL, IterTimeModel, LLAMA3_70B_KV,
    ModelKVSpec, PrefillTimeModel,
)
from repro.core.jaxutil import enable_f64
from repro.cluster.topology import FatTree, MAX_PATH_LEN, make_instances, make_nic_policy
from repro.traces.mooncake import generate_trace

BIG = 1e30
_SEQ_LIM = np.int64(1) << 32


# ----------------------------------------------------------- cohort step
def cohort_step(tokens, out_len, inst, seq, grown, live, inst_cohort, pinned,
                *, kv_per_token: float, exact_clamp: bool = True):
    """One continuous-batching iteration over the request table, jitted.

    The array-op core of ``InstancePlane._step_rows_vector``: every live
    row of an iterating instance gains one token, pins ``kv_per_token``
    more bytes on its instance, and rows reaching ``out_len`` finish,
    releasing ``grown`` bytes clamped at zero *in admission order per
    instance* — the order the reference engine's float accounting depends
    on.  ``exact_clamp=True`` reproduces that sequence with a
    ``lax.scan`` over (instance, seq)-sorted rows (bit-exact vs the
    NumPy plane, see ``tests/test_scenarioplane.py``);
    ``exact_clamp=False`` fuses the release into one segment-sum +
    single clamp (order-free, what the fluid sweep uses).

    Shapes: rows ``(R,)``; ``inst_cohort`` ``(K,)`` bool (instances
    iterating now); ``pinned`` ``(K + 1,)`` with a pad accumulator slot.
    Returns ``(tokens, live, pinned, first, fin, fin_per_inst)``.
    """
    import jax
    import jax.numpy as jnp

    k = inst_cohort.shape[0]
    inst_c = jnp.clip(inst, 0, k - 1)
    rows = live & inst_cohort[inst_c]
    tokens = jnp.where(rows, tokens + 1, tokens)
    first = rows & (tokens == 1)
    # Equal-sized per-row increments: scatter-add order cannot change the
    # per-instance float accumulation (mirrors np.add.at's sequence).
    tgt = jnp.where(rows, inst_c, k)
    pinned = pinned.at[tgt].add(jnp.asarray(kv_per_token, pinned.dtype))
    fin = rows & (tokens >= out_len)
    if exact_clamp:
        key = jnp.where(
            fin, inst_c.astype(jnp.int64) * _SEQ_LIM + seq.astype(jnp.int64),
            jnp.iinfo(jnp.int64).max)
        order = jnp.argsort(key, stable=True)

        def _clamp(p, r):
            isf = fin[r]
            s = jnp.where(isf, inst_c[r], k)
            cur = p[s]
            new = jnp.maximum(0.0, cur - grown[r])
            return p.at[s].set(jnp.where(isf, new, cur)), None

        pinned, _ = jax.lax.scan(_clamp, pinned, order)
    else:
        rel = jnp.zeros_like(pinned).at[jnp.where(fin, inst_c, k)].add(grown)
        pinned = jnp.maximum(0.0, pinned - rel)
    live = live & ~fin
    fin_per_inst = jnp.zeros(k + 1, jnp.int64).at[
        jnp.where(fin, inst_c, k)].add(1)[:k]
    return tokens, live, pinned, first, fin, fin_per_inst


_COHORT_JIT = None


def cohort_step_jit(*args, **kwargs):
    """Jitted :func:`cohort_step` (recompiles per shape; ``kv_per_token``
    rides as a traced operand so values don't retrigger compilation)."""
    global _COHORT_JIT
    if _COHORT_JIT is None:
        import jax

        _COHORT_JIT = jax.jit(cohort_step, static_argnames=("exact_clamp",))
    return _COHORT_JIT(*args, **kwargs)


# ------------------------------------------------------------- scenarios
@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """One cell of a what-if grid (mirrors the SimConfig knobs the fluid
    engine models).  ``rewires`` is a schedule of ``(time, {tier: scale})``
    multiplicative capacity edits (the OCS timeline)."""

    seed: int = 0
    scheduler: str = "netkv-full"   # "cla" | "netkv-static" | "netkv-full"
    profile: str = "chatbot"
    target_rps: float = 16.0
    warmup: float = 2.0
    measure: float = 8.0
    drain: float = 4.0
    chunk_tokens: int | None = None
    kv_streaming: bool = False
    nic_policy: str = "hash"
    background: float = 0.0
    rewires: Sequence[tuple] = ()
    # cluster shape (must match across one sweep: one batched program)
    n_pods: int = 2
    racks_per_pod: int = 2
    servers_per_rack: int = 2
    gpus_per_server: int = 8
    nics_per_server: int = 1
    tp: int = 4
    n_prefill: int = 4
    beta_max: int = 64
    hbm_free_per_gpu: float = 45e9
    m_min: float = 2e9
    kv_spec: ModelKVSpec = LLAMA3_70B_KV
    iter_model: IterTimeModel = H100_TP4_ITER
    prefill_model: PrefillTimeModel = H100_TP4_PREFILL
    # CacheLoadAware weights (only read when scheduler == "cla")
    w_cache: float = 1.0
    w_load: float = 1.0

    @property
    def duration(self) -> float:
        return self.warmup + self.measure

    @property
    def horizon(self) -> float:
        return self.duration + self.drain

    def tree_shape(self) -> tuple:
        return (self.n_pods, self.racks_per_pod, self.servers_per_rack,
                self.gpus_per_server, self.nics_per_server, self.tp,
                self.n_prefill)


_SCHED_FLAGS = {
    # (use_xfer, use_cong): cla scores load only; netkv-static prices
    # transfers at raw tier bandwidth; netkv-full adds congestion +
    # self-contention (Eq. (4)).
    "cla": (0.0, 0.0),
    "netkv-static": (1.0, 0.0),
    "netkv-full": (1.0, 1.0),
}


class ScenarioPlane:
    """Batched fluid scenario engine: prep on host, sweep as one program.

    ``backend`` selects the water-filling inner solver exactly as
    ``netkv-full``'s scorer does: ``"jax"`` (default, f64) or ``"pallas"``
    (TPU kernel for the share/argmin reduction; interpret mode off-TPU).
    """

    def __init__(self, scenarios: Sequence[ScenarioSpec], *, dt: float = 0.01,
                 backend: str = "jax", max_requests: int | None = None):
        enable_f64()
        if not scenarios:
            raise ValueError("need at least one scenario")
        if backend not in ("jax", "pallas"):
            raise ValueError(f"unknown ScenarioPlane backend {backend!r}")
        shapes = {s.tree_shape() for s in scenarios}
        if len(shapes) != 1:
            raise ValueError("all scenarios in one sweep must share a "
                             f"cluster shape; got {sorted(shapes)}")
        horizons = {s.horizon for s in scenarios}
        if len(horizons) != 1:
            raise ValueError("all scenarios in one sweep must share "
                             "warmup+measure+drain (one step count)")
        self.scenarios = list(scenarios)
        self.dt = float(dt)
        self.backend = backend
        self.n_steps = int(math.ceil(scenarios[0].horizon / self.dt))
        self._prep(max_requests)

    # ------------------------------------------------------------- host prep
    def _prep(self, max_requests: int | None) -> None:
        s0 = self.scenarios[0]
        tree = FatTree(
            s0.n_pods, s0.racks_per_pod, s0.servers_per_rack,
            s0.gpus_per_server, nics_per_server=s0.nics_per_server)
        pre_meta, dec_meta = make_instances(tree, tp=s0.tp,
                                            n_prefill=s0.n_prefill)
        self.tree = tree
        self.n_prefill = len(pre_meta)
        self.n_decode = len(dec_meta)
        p_srv = [i.server for i in pre_meta]
        d_srv = [i.server for i in dec_meta]
        p_idx = np.array([tree.server_index(s) for s in p_srv], np.int64)
        d_idx = np.array([tree.server_index(s) for s in d_srv], np.int64)
        self.tier_pd = tree.tier_vec(p_idx[:, None], d_idx[None, :])

        per_scn = []
        for spec in self.scenarios:
            reqs = generate_trace(spec.profile, duration=spec.duration,
                                  target_rps=spec.target_rps, seed=spec.seed)
            per_scn.append(self._prep_one(spec, reqs, tree, p_srv, d_srv))
        r_max = max(p["arrival"].size for p in per_scn)
        if max_requests is not None:
            if max_requests < r_max:
                raise ValueError(
                    f"max_requests={max_requests} < largest trace {r_max}")
            r_max = max_requests
        self.max_requests = r_max

        def pad(key, fill, dtype):
            out = np.full((len(per_scn), r_max), fill, dtype)
            for i, p in enumerate(per_scn):
                out[i, : p[key].size] = p[key]
            return out

        self.arrival = pad("arrival", np.inf, np.float64)
        self.s_eff = pad("s_eff", 0.0, np.float64)
        self.out_len = pad("out_len", 1, np.int64)
        self.slo = pad("slo", np.inf, np.float64)
        self.src_p = pad("src_p", 0, np.int64)
        self.prefill_end = pad("prefill_end", np.inf, np.float64)
        self.xfer_ready = pad("xfer_ready", np.inf, np.float64)
        self.path_table = np.stack([p["path_table"] for p in per_scn])
        self.bw_mult = np.stack([p["bw_mult"] for p in per_scn])
        self.bg_util = np.stack([p["bg_util"] for p in per_scn])
        self.link_cap = np.stack([p["link_cap"] for p in per_scn])
        self.tier_lat = np.stack([p["tier_lat"] for p in per_scn])
        # Compact the link axis to links the prefill->decode paths actually
        # cross: water-filling cost scales with (R, L) and a 64-GPU tree has
        # ~120 links of which the path tables touch only a fraction.  One
        # representative link per populated tier is always kept so the
        # derived p50 tier-bandwidth summary stays defined (capacities are
        # uniform per tier here, so the p50 is unchanged by the subset).
        used = np.unique(self.path_table)
        used = used[used < tree.n_links].astype(np.int64)
        for t in range(4):
            tier_ids = np.nonzero(tree.link_tier == t)[0]
            if tier_ids.size and not np.any(np.isin(tier_ids, used)):
                used = np.append(used, tier_ids[:1])
        used = np.unique(used)
        remap = np.full(tree.n_links + 1, used.size, np.int64)
        remap[used] = np.arange(used.size)
        self.link_ids = used                       # compact -> global id
        self.path_table = remap[self.path_table].astype(np.int32)
        self.link_cap = self.link_cap[:, used]
        self._link_tier_c = np.asarray(tree.link_tier)[used]
        flags = np.array([_SCHED_FLAGS[s.scheduler] for s in self.scenarios],
                         np.float64)
        self.use_xfer, self.use_cong = flags[:, 0], flags[:, 1]
        as_arr = lambda f, d=np.float64: np.array(
            [f(s) for s in self.scenarios], d)
        self.beta_max = as_arr(lambda s: s.beta_max)
        self.mem_total = as_arr(lambda s: s.hbm_free_per_gpu * s.tp)
        self.m_min = as_arr(lambda s: s.m_min)
        self.kpt = as_arr(lambda s: float(s.kv_spec.kv_bytes_per_token))
        self.iter_a = as_arr(lambda s: s.iter_model.a)
        self.iter_b = as_arr(lambda s: s.iter_model.b)
        self.w_cache = as_arr(lambda s: s.w_cache)
        self.w_load = as_arr(lambda s: s.w_load)
        self.warmup_arr = as_arr(lambda s: s.warmup)
        self.measure_arr = as_arr(lambda s: s.measure)
        self.seeds = np.array([s.seed for s in self.scenarios], np.uint32)

    def _prep_one(self, spec, reqs, tree, p_srv, d_srv) -> dict:
        """Host-side per-scenario tables: trace columns, serial prefill
        queueing, chunk-streamed transfer readiness, ECMP path table."""
        n = len(reqs)
        arrival = np.array([r.arrival for r in reqs], np.float64)
        in_len = np.array([r.input_len for r in reqs], np.int64)
        out_len = np.maximum(
            np.array([r.output_len for r in reqs], np.int64), 1)
        slo = np.array([r.slo for r in reqs], np.float64)
        s_eff = np.array(
            [float(spec.kv_spec.kv_bytes(int(l))) for l in in_len], np.float64)
        # Round-robin prefill assignment; serial per-instance prefill queue
        # (chunking changes *readiness*, not total prefill seconds).
        src_p = np.arange(n, dtype=np.int64) % len(p_srv)
        busy = np.zeros(len(p_srv), np.float64)
        pf_start = np.zeros(n, np.float64)
        pf_end = np.zeros(n, np.float64)
        pm = spec.prefill_model
        for j in range(n):
            p = src_p[j]
            pf_start[j] = max(arrival[j], busy[p])
            busy[p] = pf_start[j] + pm(int(in_len[j]))
            pf_end[j] = busy[p]
        if spec.chunk_tokens:
            # ChunkPlane semantics: the decode instance is selected (and,
            # when streaming, bytes start moving) at first-chunk readiness.
            first_chunk = pf_start + np.array(
                [pm(min(int(l), int(spec.chunk_tokens))) for l in in_len])
            ready = first_chunk if spec.kv_streaming else pf_end
        else:
            ready = pf_end
        # ECMP path table: one uplink draw per (prefill, decode) pair from
        # the scenario's RNG stream, NIC pair from the scenario's policy.
        rng = np.random.default_rng(spec.seed)
        policy = make_nic_policy(spec.nic_policy)
        policy.bind(lambda lids: np.zeros(np.shape(lids), np.int64))
        pt = np.full((len(p_srv), len(d_srv), MAX_PATH_LEN), tree.n_links,
                     np.int32)
        for pi, ps in enumerate(p_srv):
            for di, ds in enumerate(d_srv):
                t = tree.tier(ps, ds)
                nics = (0, 0) if t == 0 else policy.pick(
                    tree, tree.server_index(ps), tree.server_index(ds), rng)
                row, _ = tree.path_row(ps, ds, rng, nics=nics)
                pt[pi, di] = np.where(row < 0, tree.n_links, row)
        # Capacity timeline: cumulative multiplicative tier scaling per step.
        mult = np.ones((self.n_steps, 4), np.float64)
        cur = np.ones(4, np.float64)
        edits = sorted((float(t), dict(sc)) for t, sc in spec.rewires)
        k0 = 0
        for t_ev, sc in edits:
            k1 = min(self.n_steps, max(0, int(math.ceil(t_ev / self.dt))))
            mult[k0:k1] = cur
            for tier, f in sc.items():
                cur[int(tier)] *= float(f)
            k0 = k1
        mult[k0:] = cur
        bg = np.array([
            0.0 if t == 0 else min(max(float(spec.background), 0.0), 0.95)
            for t in range(4)], np.float64)
        return dict(
            arrival=arrival, s_eff=s_eff, out_len=out_len, slo=slo,
            src_p=src_p, prefill_end=pf_end, xfer_ready=ready,
            path_table=pt, bw_mult=mult, bg_util=bg,
            link_cap=tree.link_capacity.copy(),
            tier_lat=np.array([tree.tier_latency[t] for t in range(4)],
                              np.float64),
        )

    # ------------------------------------------------------------- the sweep
    def sweep(self, *, detail: bool = False) -> dict:
        """Run every scenario in one jitted, vmapped program.

        Returns a dict of per-scenario summary arrays (``ttft_mean``,
        ``ttft_p50/p95/p99``, ``tbt_mean``, ``slo_attainment``,
        ``goodput_rps``, ``n_measured``, ``n_served``); with
        ``detail=True`` adds per-request ``t_first``/``t_fin``/``tokens``
        (the vmap-consistency test surface).
        """
        out = self._sweep_jit()(*self._sweep_args())
        res = {k: np.asarray(v) for k, v in out.items()}
        if not detail:
            for k in ("t_first", "t_fin", "tokens"):
                res.pop(k)
        return res

    def _sweep_args(self) -> tuple:
        """The sweep program's arguments, scenario axis first."""
        import jax
        import jax.numpy as jnp

        return (
            jnp.asarray(self.arrival), jnp.asarray(self.s_eff),
            jnp.asarray(self.out_len), jnp.asarray(self.slo),
            jnp.asarray(self.src_p), jnp.asarray(self.prefill_end),
            jnp.asarray(self.xfer_ready), jnp.asarray(self.path_table),
            jnp.asarray(self.bw_mult), jnp.asarray(self.bg_util),
            jnp.asarray(self.link_cap), jnp.asarray(self.tier_lat),
            jnp.asarray(self.use_xfer), jnp.asarray(self.use_cong),
            jnp.asarray(self.beta_max), jnp.asarray(self.mem_total),
            jnp.asarray(self.m_min), jnp.asarray(self.kpt),
            jnp.asarray(self.iter_a), jnp.asarray(self.iter_b),
            jnp.asarray(self.w_cache), jnp.asarray(self.w_load),
            jnp.asarray(self.warmup_arr), jnp.asarray(self.measure_arr),
            jax.vmap(jax.random.PRNGKey)(jnp.asarray(self.seeds)),
        )

    def _sweep_jit(self):
        import jax

        from repro.kernels.ops import interpret_mode

        if not hasattr(self, "_jitted"):
            one = lambda *a: _run_one(
                *a, tier_pd=self.tier_pd, dt=self.dt, n_steps=self.n_steps,
                use_pallas=(self.backend == "pallas"),
                interpret=interpret_mode(),
                link_tier=self._link_tier_c)
            self._jitted = jax.jit(jax.vmap(one))
        return self._jitted


def _run_one(arrival, s_eff, out_len, slo, src_p, prefill_end, xfer_ready,
             path_table, bw_mult, bg_util, link_cap, tier_lat, use_xfer,
             use_cong, beta_max, mem_total, m_min, kpt, iter_a, iter_b,
             w_cache, w_load, warmup, measure, key, *, tier_pd, dt, n_steps,
             use_pallas, interpret, link_tier):
    """One scenario's fluid run (traced once, vmapped over the batch)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.waterfill import waterfill_rates_fast

    R = arrival.shape[0]
    P, D, H = path_table.shape
    L = link_cap.shape[0]
    tier_pd = jnp.asarray(tier_pd, jnp.int32)
    link_tier_j = jnp.asarray(link_tier, jnp.int32)
    valid = jnp.isfinite(arrival)
    base_bw = _tier_base_bw(link_cap, link_tier_j)   # (4,) p50 per tier
    # Per-scenario RNG: a static tie-break jitter on the (request, decode)
    # cost surface, standing in for the event scheduler's arrival-order
    # tie-breaking (identical between batched and solo runs of a seed).
    jitter = jax.random.uniform(key, (R, D), jnp.float64) * 1e-9
    resid_base = link_cap * (1.0 - bg_util[link_tier_j])
    # Flow->link hop counts per (prefill, decode) pair, built once at trace
    # time: per-step routing is then a (R, L+1) gather instead of a one-hot
    # incidence rebuild, which dominated the vmapped step cost on CPU.
    inc_pd = (path_table[:, :, :, None]
              == jnp.arange(L + 1, dtype=path_table.dtype)[None, None,
                                                           None, :]
              ).sum(axis=2).astype(jnp.float64)
    inc_pd = inc_pd.at[:, :, L].set(0.0)

    def step(k, st):
        (tokens, live, inst, r_tier, xfer_rem, xfer_on, arrived, admitted,
         t_first, t_fin, pinned, credit, d_queued, tier_infl) = st
        t0 = k * dt
        t1 = t0 + dt
        d_active = _seg_count(inst, live, D)
        # --- A: decode-instance selection (Eqs. (2)-(7)) ------------------
        ready = valid & (xfer_ready <= t0) & (inst < 0)
        free_d = mem_total - pinned[:D]
        feas = free_d[None, :] >= (s_eff[:, None] + m_min)
        tier_rd = tier_pd[src_p]                      # (R, D)
        tier_bw = base_bw * bw_mult[k]                # derived p50 summary
        infl = jnp.where(use_cong > 0.5, tier_infl.astype(jnp.float64),
                         jnp.zeros(4))
        cong = jnp.where(use_cong > 0.5, bg_util, jnp.zeros(4))
        beff = tier_bw * (1.0 - cong) / (1.0 + infl)  # Eq. (4), per tier
        t_xfer = s_eff[:, None] / jnp.maximum(beff[tier_rd], 1e-9) \
            + tier_lat[tier_rd]                       # Eq. (3)
        t_it = iter_a + iter_b * d_active             # (D,)
        blocked = jnp.maximum(
            0.0, d_queued + d_active - beta_max)      # Eq. (6)
        t_queue = blocked * t_it
        t_dec = iter_a + iter_b * (d_active + 1.0)    # Eq. (7)
        cost_net = t_xfer + (t_queue + t_dec)[None, :]
        cost_cla = w_cache * 1.0 + w_load * (
            (d_active + d_queued) / jnp.maximum(beta_max, 1.0))[None, :]
        cost = jnp.where(use_xfer > 0.5, cost_net, cost_cla) + jitter
        cost = jnp.where(feas, cost, BIG)
        best = jnp.argmin(cost, axis=1).astype(jnp.int32)
        ok = ready & (cost[jnp.arange(R), best] < BIG * 0.5)
        # Sequential-decision emulation: at most max(1, open slots) new
        # dispatches per instance per dt; the rest retry next step.
        onehot = (ok[:, None] & (best[:, None] == jnp.arange(D)[None, :]))
        rank = (jnp.cumsum(onehot, axis=0) - onehot.astype(jnp.int64))[
            jnp.arange(R), best]
        slots = jnp.maximum(beta_max - d_active - d_queued, 1.0)
        take = ok & (rank < slots[best])
        inst = jnp.where(take, best, inst)
        new_tier = tier_rd[jnp.arange(R), best]
        r_tier = jnp.where(take, new_tier, r_tier)
        xfer_on = xfer_on | take
        d_queued = d_queued + _seg_count(best, take, D)
        tier_infl = tier_infl + _seg_count(new_tier, take, 4)
        # --- B: max-min fair transfer drain (the jitted water-filling) ----
        caps = jnp.append(resid_base * bw_mult[k][link_tier_j], jnp.inf)
        # Parallel-bottleneck variant: identical max-min allocation, but
        # ~levels while_loop rounds instead of one per concurrent transfer
        # (the sweep's dominant cost; see kernels/waterfill.py).
        nhops = inc_pd[src_p, jnp.clip(inst, 0, D - 1)]
        rates = waterfill_rates_fast(
            None, caps, xfer_on, nhops=nhops,
            use_pallas=use_pallas, interpret=interpret)
        xfer_rem = jnp.where(
            xfer_on, jnp.maximum(xfer_rem - rates.astype(jnp.float64) * dt,
                                 0.0), xfer_rem)
        done = xfer_on & (xfer_rem <= 1.0) & (t1 >= prefill_end)
        xfer_on = xfer_on & ~done
        arrived = arrived | done
        tier_infl = tier_infl - _seg_count(r_tier, done, 4)
        # --- C: FCFS admission into the decode batch ----------------------
        wait = arrived & ~admitted
        inst_c = jnp.clip(inst, 0, D - 1)
        oh_w = wait[:, None] & (inst_c[:, None] == jnp.arange(D)[None, :])
        rank_w = (jnp.cumsum(oh_w, axis=0) - oh_w.astype(jnp.int64))[
            jnp.arange(R), inst_c]
        cum_mem = (jnp.cumsum(oh_w * s_eff[:, None], axis=0)
                   - oh_w * s_eff[:, None])[jnp.arange(R), inst_c]
        admit = wait & (rank_w < (beta_max - d_active)[inst_c]) & (
            pinned[inst_c] + cum_mem + s_eff <= mem_total - m_min)
        admitted = admitted | admit
        live = live | admit
        pinned = pinned.at[jnp.where(admit, inst_c, D)].add(
            jnp.where(admit, s_eff, 0.0))
        d_queued = d_queued - _seg_count(inst_c, admit, D)
        d_active = _seg_count(inst, live, D)
        # --- D: continuous-batching iteration clock + cohort step ---------
        t_it = iter_a + iter_b * d_active
        credit = credit + dt
        fire = (credit >= t_it) & (d_active > 0)
        credit = jnp.where(fire, credit - t_it, credit)
        tokens, live, pinned, first, fin, _ = cohort_step(
            tokens, out_len, inst, jnp.arange(R, dtype=jnp.int64),
            s_eff + out_len * kpt, live, fire, pinned,
            kv_per_token=kpt, exact_clamp=False)
        t_first = jnp.where(first & (t_first < 0), t1, t_first)
        t_fin = jnp.where(fin, t1, t_fin)
        return (tokens, live, inst, r_tier, xfer_rem, xfer_on, arrived,
                admitted, t_first, t_fin, pinned, credit, d_queued, tier_infl)

    st0 = (
        jnp.zeros(R, jnp.int64),                    # tokens
        jnp.zeros(R, bool),                         # live (decoding)
        jnp.full(R, -1, jnp.int32),                 # decode instance
        jnp.zeros(R, jnp.int32),                    # transfer tier
        s_eff.astype(jnp.float64),                  # xfer bytes remaining
        jnp.zeros(R, bool),                         # transfer active
        jnp.zeros(R, bool),                         # KV landed
        jnp.zeros(R, bool),                         # admitted to batch
        jnp.full(R, -1.0, jnp.float64),             # first-token time
        jnp.full(R, -1.0, jnp.float64),             # finish time
        jnp.zeros(D + 1, jnp.float64),              # pinned KV (+pad slot)
        jnp.zeros(D, jnp.float64),                  # iteration credit
        jnp.zeros(D, jnp.int64),                    # scheduled, not admitted
        jnp.zeros(4, jnp.int64),                    # own in-flight per tier
    )
    st = jax.lax.fori_loop(0, n_steps, step, st0)
    (tokens, live, inst, _, _, _, _, admitted, t_first, t_fin, *_rest) = st
    return _summarize(arrival, slo, out_len, t_first, t_fin, tokens,
                      warmup, measure, valid)


def _seg_count(idx, mask, n):
    import jax.numpy as jnp

    return jnp.zeros(n + 1, jnp.int64).at[
        jnp.where(mask, jnp.clip(idx, 0, n - 1), n)].add(1)[:n]


def _tier_base_bw(link_cap, link_tier):
    """p50 per-tier capacity of the columnar link table (the oracle's
    derived tier_bandwidth summary, computed in-program)."""
    import jax.numpy as jnp

    out = []
    for t in range(4):
        sel = link_tier == t
        big = jnp.where(sel, link_cap, jnp.nan)
        out.append(jnp.nanmedian(big))
    return jnp.stack(out)


def _masked_pct(x, mask, q, r):
    import jax.numpy as jnp

    n = mask.sum()
    s = jnp.sort(jnp.where(mask, x, jnp.inf))
    pos = (q / 100.0) * jnp.maximum(n - 1, 0)
    lo = jnp.clip(jnp.floor(pos).astype(jnp.int64), 0, r - 1)
    hi = jnp.clip(jnp.ceil(pos).astype(jnp.int64), 0, r - 1)
    frac = pos - jnp.floor(pos)
    v = s[lo] * (1.0 - frac) + s[hi] * frac
    return jnp.where(n > 0, v, jnp.nan)


def _summarize(arrival, slo, out_len, t_first, t_fin, tokens, warmup,
               measure, valid):
    import jax.numpy as jnp

    r = arrival.shape[0]
    meas = valid & (arrival >= warmup) & (arrival < warmup + measure)
    served = meas & (t_first >= 0)
    ttft = jnp.where(served, t_first - arrival, jnp.inf)
    fin_ok = meas & (t_fin >= 0) & (out_len > 1)
    tbt = jnp.where(fin_ok, (t_fin - t_first)
                    / jnp.maximum(out_len - 1, 1).astype(jnp.float64),
                    jnp.inf)
    n_meas = meas.sum()
    n_served = served.sum()
    slo_ok = (served & (ttft <= slo)).sum()
    mean = lambda v, m: jnp.where(
        m.sum() > 0, jnp.where(m, v, 0.0).sum() / jnp.maximum(m.sum(), 1),
        jnp.nan)
    return dict(
        n_measured=n_meas,
        n_served=n_served,
        ttft_mean=mean(ttft, served),
        ttft_p50=_masked_pct(ttft, served, 50.0, r),
        ttft_p95=_masked_pct(ttft, served, 95.0, r),
        ttft_p99=_masked_pct(ttft, served, 99.0, r),
        tbt_mean=mean(tbt, fin_ok),
        slo_attainment=jnp.where(
            n_meas > 0, slo_ok / jnp.maximum(n_meas, 1), jnp.nan),
        goodput_rps=slo_ok / jnp.maximum(measure, 1e-9),
        t_first=t_first,
        t_fin=t_fin,
        tokens=tokens,
    )
