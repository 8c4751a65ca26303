"""Run NetKV's three device paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: simulator, serving, sweep
    python chip_smoke.py --chips 4   # four chips: the serving path only

Phases (one process; each prints one line with its numbers and compiles):

* ``sim``: ``run_sim`` on the paper's 64-GPU cell (``SimConfig`` defaults,
  rag at 0.8 of capacity, seed 0) with ``netkv-full`` scoring through the
  compiled Pallas kernel, then a short trace of same-arrival bursts that
  dispatch as cohorts of several rows.  Every kernel call is checked, row
  by row, against the NumPy backend's f64 Eq. (2)-(7) on the same
  snapshot: same winner, or a winner whose cost is within the view-parity
  tests' tolerance.
* ``serve``: ``DisaggregatedCluster`` at the full width of smollm-135m
  (bf16, random weights from the seed), 4 requests of 1024-token prompts x
  32 new tokens, half of them sharing a 512-token prefix.  The tokens must
  equal the same engines' prefill -> decode without pack/unpack, the bytes
  shipped must equal Eq. (1) for the pages the decode engine did not hold,
  and ``kv_pack``/``kv_unpack`` must be Mosaic kernels.
* ``sweep``: ``ScenarioPlane.sweep()`` on a small exp11 grid with the jax
  and the pallas water-filling backends; their summaries must agree.  It
  runs last because it turns ``jax_enable_x64`` on for the process.

With ``--chips 4`` the serving phase alone runs with the prefill engine on
device 0 and the decode engines on devices 1-3, round robin so that every
decode chip receives KV, and its tokens must equal the same requests
served on device 0 alone.

The script fails, printing no result, when JAX's first device is not a TPU
or when the ``repro`` package is not beside it.  Its last line is one JSON
object naming the device.  The compile cache is ``JAX_COMPILATION_CACHE_DIR``
when that is set, else ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
COST_RTOL = 1e-5          # tests/test_view_parity.py: pallas vs numpy winner
SWEEP_RTOL, SWEEP_ATOL = 1e-9, 1e-6   # tests/test_scenarioplane.py fast solver
PROMPT, PREFIX, NEW_TOKENS, CACHE_LEN = 1024, 512, 32, 2048


def check(ok, what="") -> None:
    """Fail the run; unlike ``assert``, not stripped by ``python -O``."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class CompileCounter:
    """Counts backend compiles (persistent-cache hits included, which
    report their load time) through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n, self.secs, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> tuple:
        return self.n, self.secs, self.hits

    def since(self, mark: tuple) -> str:
        n, secs, hits = mark
        return (f"compiles={self.n - n} compile_s={self.secs - secs:.3f} "
                f"cache_hits={self.hits - hits}")


# ------------------------------------------------------------------ sim
@contextlib.contextmanager
def kernel_calls():
    """Record every ``netkv_score_cohort`` call (inputs and outputs)."""
    # The package re-exports a function named netkv_score over the module.
    ns = importlib.import_module("repro.kernels.netkv_score")
    calls, real = [], ns.netkv_score_cohort

    def record(*args, **kw):
        # Copy now: the pool columns are live views the simulator mutates.
        snapshot = [np.array(a) for a in args]
        out = real(*args, **kw)
        # The caller: CohortSelector._build_pallas, or netkv_score (the
        # single-row view NetKVFull._select_pallas calls).
        calls.append((snapshot, kw, out, sys._getframe(1).f_code.co_name))
        return out

    ns.netkv_score_cohort = record
    try:
        yield calls
    finally:
        ns.netkv_score_cohort = real


def check_kernel_call(args, kw, out) -> tuple[int, int, float]:
    """(rows, rows whose winner agrees with the NumPy backend, largest
    relative f32-vs-f64 error of a winning cost) for one kernel call."""
    from repro.core.cost import IterTimeModel
    from repro.core.schedulers import v_iter_time, v_s_eff, v_transfer_time
    from repro.kernels.netkv_score import BIG

    check(not kw.get("numpy") and kw["interpret"] is False, kw)
    (free, queued, batch, hit_rows, tier_rows, healthy, scale, bw, lat, cong,
     infl_rows) = (np.asarray(a, np.float64) for a in args)
    costs, best = (np.asarray(o) for o in out)
    model = IterTimeModel(a=kw["iter_a"], b=kw["iter_b"])
    # NetKVFull's NumPy backend: Eq. (6) and Eq. (7), straggler-scaled.
    blocked = np.maximum(0, queued - (kw["beta_max"] - batch))
    t_q = scale * (blocked * v_iter_time(model, batch))
    t_d = scale * v_iter_time(model, batch + 1)
    s_r = np.ravel(kw["s_r"])
    l_r = np.ravel(kw["input_len"])
    state = np.zeros_like(s_r) if kw.get("state_bytes") is None \
        else np.ravel(kw["state_bytes"])
    agree, err = 0, 0.0
    for i in range(hit_rows.shape[0]):
        s_eff = v_s_eff(float(s_r[i]), hit_rows[i], int(l_r[i]), float(state[i]))
        ok = (healthy > 0.5) & (free >= s_eff + kw["m_min"])
        t_x = v_transfer_time(s_eff, tier_rows[i].astype(np.int64), bw, cong,
                              infl_rows[i], lat)
        cost = t_x + t_q + t_d
        j = int(best[i])
        if not ok.any():
            agree += int(costs[i, j] >= BIG / 2)
            continue
        j_np = int(np.flatnonzero(ok)[np.argmin(cost[ok])])
        if j == j_np or (ok[j] and abs(cost[j] - cost[j_np])
                         <= COST_RTOL * max(abs(cost[j_np]), 1e-9)):
            agree += 1
        err = max(err, abs(float(costs[i, j]) - cost[j]) / max(abs(cost[j]), 1e-9))
    return hit_rows.shape[0], agree, err


def burst_trace(bursts: int = 12, width: int = 4) -> list:
    """Bursts of ``width`` equal prompts arriving together: their prefills
    finish at one instant and dispatch as one cohort through
    ``CohortSelector`` (Poisson arrivals almost never share a timestamp)."""
    from repro.traces.mooncake import Request

    return [Request(b * width + i, 0.1 + 0.4 * b, 1024, 64,
                    tuple(f"b{b}-{i}-{j}" for j in range(8)), b * width + i, 1.0)
            for b in range(bursts) for i in range(width)]


def phase_sim(counter: CompileCounter) -> None:
    from repro.sim import SimConfig, run_sim
    from repro.traces import generate_trace, profile_capacity

    pallas = {"backend": "pallas"}
    mark, t0 = counter.mark(), time.perf_counter()
    trace = generate_trace("rag", duration=22.0,
                           target_rps=0.8 * profile_capacity("rag"), seed=0)
    bursts = burst_trace()
    with kernel_calls() as calls:
        m = run_sim(SimConfig(seed=0, scheduler_kwargs=pallas), trace)
        n_paper = len(calls)
        # The paper cell's Poisson arrivals score one request per call;
        # the bursts drive the cohort kernel at R > 1.
        run_sim(SimConfig(seed=0, warmup=0.5, measure=4.0,
                          scheduler_kwargs=pallas), bursts)
    wall = time.perf_counter() - t0
    rows = agree = 0
    err = 0.0
    sizes, callers = set(), {}
    for args, kw, out, caller in calls:
        r, a, e = check_kernel_call(args, kw, out)
        rows, agree, err = rows + r, agree + a, max(err, e)
        sizes.add(r)
        callers[caller] = callers.get(caller, 0) + 1
    m_np = run_sim(SimConfig(seed=0), trace)
    check(rows > 0, "no row was scored on the device")
    check(set(callers) == {"netkv_score", "_build_pallas"}, callers)
    check(max(sizes) > 1, "no cohort of several rows was scored")
    check(agree == rows, f"{rows - agree} of {rows} winners disagree with numpy")
    check(err <= COST_RTOL, err)
    check(np.isfinite([m.ttft_mean, m.ttft_p99]).all())
    print(f"sim: requests={len(trace)} kernel_calls={n_paper} "
          f"burst_requests={len(bursts)} "
          f"burst_kernel_calls={len(calls) - n_paper} "
          f"calls_by_caller={callers} device_rows={rows} "
          f"cohort_sizes={sorted(sizes)} "
          f"winners_agree={agree}/{rows} max_cost_rel_err={err:.3e} "
          f"ttft_mean_s={m.ttft_mean!r} ttft_p99_s={m.ttft_p99!r} "
          f"numpy_ttft_mean_s={m_np.ttft_mean!r} "
          f"numpy_ttft_p99_s={m_np.ttft_p99!r} wall_s={wall:.3f} "
          f"{counter.since(mark)}", flush=True)


# ---------------------------------------------------------------- serve
def serve_requests(vocab: int, shared=(0, 2), seed: int = 0) -> list:
    """Four requests; those numbered in ``shared`` open with one common
    PREFIX-token prefix."""
    from repro.serving import ServeRequest

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, PREFIX)
    reqs = []
    for i in range(4):
        prompt = (np.concatenate([prefix, rng.integers(0, vocab, PROMPT - PREFIX)])
                  if i in shared else rng.integers(0, vocab, PROMPT))
        reqs.append(ServeRequest(i, prompt, max_new=NEW_TOKENS, arrival=i * 0.05))
    return reqs


def direct_tokens(cluster, req, res) -> list[int]:
    """The request on the engines ``res`` names, prefill cache admitted
    as is: no pack, no unpack."""
    pe = next(p for p in cluster.prefill if p.instance_id == res.prefill_instance)
    de = next(d for d in cluster.decode if d.instance_id == res.decode_instance)
    pre = pe.run(req.request_id, req.prompt)
    de.admit(req.request_id, pre, req.max_new)
    toks = [pre.first_token]
    while de.beta:
        toks.extend(t for _, t in de.step())
    return toks


def check_transfer(spec, results) -> int:
    """Shipped bytes == Eq. (1) over the non-hit pages; kv_pack/kv_unpack
    compiled by Mosaic at the shapes that ran.  Returns the hit pages."""
    import jax
    import jax.numpy as jnp

    from repro.core.cost import B_TOK
    from repro.kernels import ops

    kv = spec.kv_spec()
    pages = -(-PROMPT // B_TOK)
    for r in results:
        want = kv.kv_bytes_per_token * (pages - r.hit_pages) * B_TOK \
            + kv.fixed_state_bytes
        check(r.transfer_bytes == want, (r.request_id, r.transfer_bytes, want))
    m = spec.model
    page = (B_TOK, m.n_kv_heads, m.d_head)
    n_sel = m.n_periods * (pages - results[-1].hit_pages)
    pool = jax.ShapeDtypeStruct((m.n_periods * CACHE_LEN // B_TOK, *page),
                                m.compute_dtype)
    buf = jax.ShapeDtypeStruct((n_sel, *page), m.compute_dtype)
    table = jax.ShapeDtypeStruct((n_sel,), jnp.int32)
    for fn, args in ((ops.kv_pack, (pool, table)),
                     (ops.kv_unpack, (pool, buf, table))):
        check("tpu_custom_call" in fn.lower(*args).compile().as_text())
    return sum(r.hit_pages for r in results)


def phase_serve(counter: CompileCounter) -> None:
    from repro.configs import get_spec
    from repro.serving import DisaggregatedCluster

    spec = get_spec("smollm-135m")
    mark, t0 = counter.mark(), time.perf_counter()
    cluster = DisaggregatedCluster(spec.model, cache_len=CACHE_LEN)
    reqs = serve_requests(spec.model.vocab_size)
    results = cluster.serve(reqs)
    wall = time.perf_counter() - t0
    same = sum(direct_tokens(cluster, q, r) == r.tokens
               for q, r in zip(reqs, results))
    check(all(len(r.tokens) == NEW_TOKENS for r in results))
    check(same == len(reqs), f"{len(reqs) - same} requests changed in transfer")
    hits = check_transfer(spec, results)
    check(hits > 0, "no request reused a prefix on its decode engine")
    print(f"serve: model=smollm-135m dtype=bfloat16 requests={len(reqs)} "
          f"prompt={PROMPT} new={NEW_TOKENS} tokens_match_direct={same}/{len(reqs)} "
          f"bytes_eq1=ok hit_pages={hits} "
          f"bytes={[r.transfer_bytes for r in results]} "
          f"decode={[r.decode_instance for r in results]} "
          f"pack_unpack=tpu_custom_call wall_s={wall:.3f} "
          f"{counter.since(mark)}", flush=True)


def phase_serve_4(counter: CompileCounter) -> None:
    import jax

    from repro.configs import get_spec
    from repro.serving import DisaggregatedCluster

    spec = get_spec("smollm-135m")
    devs = jax.devices()[:4]
    mark, t0 = counter.mark(), time.perf_counter()
    runs = {}
    for name, on in (("4chip", devs), ("chip0", devs[:1])):
        # Round robin sends the KV to every decode chip in turn (netkv-full
        # would keep picking the one nearest the lone prefill engine);
        # requests 0 and 3 land on the same engine and share a prefix.
        cluster = DisaggregatedCluster(spec.model, scheduler="rr",
                                       cache_len=CACHE_LEN, n_prefill=1,
                                       n_decode=3, devices=on)
        device_of = {d.instance_id: d.device.id for d in cluster.decode}
        res = cluster.serve(serve_requests(spec.model.vocab_size, shared=(0, 3)))
        runs[name] = (res, [device_of[r.decode_instance] for r in res])
    wall = time.perf_counter() - t0
    (res4, used4), (res1, _) = runs["4chip"], runs["chip0"]
    check(set(used4) == {d.id for d in devs[1:]}, used4)
    same = sum(a.tokens == b.tokens and a.decode_instance == b.decode_instance
               for a, b in zip(res4, res1))
    check(same == len(res1), f"{len(res1) - same} requests differ across chips")
    hits = check_transfer(spec, res4)
    check(hits > 0, "no request reused a prefix on its decode chip")
    print(f"serve4: model=smollm-135m dtype=bfloat16 scheduler=rr "
          f"prefill_device={devs[0].id} decode_device_per_request={used4} "
          f"requests={len(res4)} tokens_match_chip0={same}/{len(res1)} "
          f"bytes_eq1=ok hit_pages={hits} "
          f"decode={[r.decode_instance for r in res4]} "
          f"pack_unpack=tpu_custom_call wall_s={wall:.3f} "
          f"{counter.since(mark)}", flush=True)


# ---------------------------------------------------------------- sweep
def phase_sweep(counter: CompileCounter) -> None:
    from repro.sim import ScenarioPlane, ScenarioSpec

    specs = [ScenarioSpec(seed=seed, scheduler=sched, target_rps=8.0,
                          warmup=1.0, measure=4.0, drain=2.0, background=0.25)
             for sched in ("cla", "netkv-full") for seed in range(2)]
    out, walls = {}, {}
    mark = counter.mark()
    for backend in ("jax", "pallas"):
        t0 = time.perf_counter()
        plane = ScenarioPlane(specs, dt=0.01, backend=backend)
        out[backend] = plane.sweep()
        walls[backend] = time.perf_counter() - t0
        # The water-fill share kernel is in the pallas program, as a
        # Mosaic call, and only there.
        lowered = plane._sweep_jit().lower(*plane._sweep_args()).as_text()
        check(("tpu_custom_call" in lowered) == (backend == "pallas"), backend)
    for key, ref in out["jax"].items():
        np.testing.assert_allclose(out["pallas"][key], ref, rtol=SWEEP_RTOL,
                                   atol=SWEEP_ATOL, err_msg=key)
    jx = out["jax"]
    check(np.isfinite(jx["ttft_mean"]).all() and (jx["n_served"] > 0).all())
    print(f"sweep: scenarios={len(specs)} backends_agree=ok "
          f"ttft_mean_s={jx['ttft_mean'].tolist()} "
          f"slo={jx['slo_attainment'].tolist()} "
          f"n_served={jx['n_served'].tolist()} "
          f"wall_s_jax={walls['jax']:.3f} wall_s_pallas={walls['pallas']:.3f} "
          f"{counter.since(mark)}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the serving path across four chips, only")
    args = ap.parse_args()
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from repro.core.jaxutil import use_compile_cache
    from repro.kernels.ops import interpret_mode

    cache_dir = use_compile_cache()
    check(not interpret_mode())
    counter = CompileCounter()
    kind = devices[0].device_kind
    print(f"setup: device={kind} count={len(devices)} compile_cache={cache_dir}",
          flush=True)
    if args.chips == 4:
        phase_serve_4(counter)
    else:
        phase_sim(counter)
        phase_serve(counter)
        phase_sweep(counter)   # last: turns x64 on for the process
    print(f"total: {counter.since((0, 0.0, 0))}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
