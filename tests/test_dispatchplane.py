"""DispatchPlane: cohort-batched selection vs sequential ``select`` calls.

Three layers of the same bit-exactness bar every plane has met:

* unit/property — a ``CohortSelector.select_row`` walk over a fuzzed cohort
  (mixed streamed/serial rows, shared prefill sources, infeasible rows)
  must reproduce the sequential ``select`` stream exactly, *including* the
  RNG tie-break draws, the round-robin cursor and the self-contention
  counters;
* kernel — ``netkv_score_cohort`` rows vs single-row ``netkv_score`` calls
  (the r==1-padded shared program) and the pallas-backend selector;
* end-to-end — ``SimConfig.dispatch_mode="plane"`` vs ``"reference"`` on
  seeded drives where same-timestamp cohorts demonstrably form, for every
  ladder policy, plus chunked/streamed prefill, faults and rewires.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import (
    CandidateState,
    ClusterView,
    CohortItem,
    H100_TP4_ITER,
    RequestInfo,
    SelfContentionTracker,
    make_scheduler,
    supports_cohort,
)
from repro.core.oracle import (
    OracleView,
    PAPER_TIER_BANDWIDTH,
    PAPER_TIER_LATENCY,
)
from repro.sim import FaultEvent, RewireEvent, SimConfig, Simulation
from repro.traces.mooncake import Request

from hybrid_shape import HYBRID_KV, STATE
from hypothesis_compat import given, settings, st

LADDER8 = ["rr", "la", "ca", "cla",
           "netkv-topo", "netkv-static", "netkv-full", "netkv-pred"]


# --------------------------------------------------------------------------
# unit / property layer
# --------------------------------------------------------------------------
def _pool(n: int, seed: int, tight: bool = False):
    """Candidates + oracle view; ``tight`` draws free memory low enough
    that some (sometimes all) candidates are infeasible for a multi-GiB
    s_eff, exercising the None-row / no-draw path."""
    rng = np.random.default_rng(seed)
    lo, hi = (0.0, 1.6e10) if tight else (1e10, 4e11)
    cands = [
        CandidateState(i, float(rng.uniform(lo, hi)),
                       int(rng.integers(0, 8)), int(rng.integers(0, 64)),
                       0.0)
        for i in range(n)
    ]
    tiers = rng.integers(0, 4, n)
    view = OracleView(lambda p, d: int(tiers[d % n]), PAPER_TIER_BANDWIDTH,
                      PAPER_TIER_LATENCY, {t: 0.2 for t in range(4)})
    return cands, view


def _cohort(r: int, n: int, seed: int, streamed: bool, state: bool = False):
    """R dispatch-ready requests: random prefix hits (including overshoot
    past input_len, which v_s_eff clips), shared prefill sources, and —
    when ``streamed`` — a mix of serial / tail-less / tailed rows; with
    ``state`` each carries the hybrid shape's fixed state."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    items = []
    for k in range(r):
        l = int(rng.integers(1, 16384))
        if state:
            req = RequestInfo(k, l, float(HYBRID_KV.kv_bytes(l)),
                              state_bytes=float(STATE))
        else:
            req = RequestInfo(k, l, float(l) * 320 * 1024)
        if streamed and rng.random() < 0.6:
            req.prefill_remaining = float(rng.uniform(0.0, 0.5))
            if rng.random() < 0.5:
                req.tail_bytes = float(rng.uniform(0, 1.5) * req.kv_bytes)
        items.append(CohortItem(req, int(rng.integers(0, 6))))
    H = rng.uniform(0, 1.25, (r, n)) * np.array(
        [it.req.input_len for it in items], np.float64)[:, None]
    return items, H


def _run_sequential(sched, cv, view, items, H, infl):
    out = []
    for k, it in enumerate(items):
        cv.hit_tokens[: cv.n] = H[k]
        d = sched.select(it.req, it.prefill_id, cv, view, infl)
        out.append(d)
        if d is not None:
            cv.apply_assignment(cv.slot_of(d.instance_id), kv_bytes=d.s_eff)
    return out


def _run_cohort(sched, cv, view, items, H, infl):
    sel = sched.select_cohort(items, cv, view, infl, hit_matrix=H.copy())
    out = []
    for k in range(len(items)):
        d = sel.select_row(k)
        out.append(d)
        if d is not None:
            cv.apply_assignment(cv.slot_of(d.instance_id), kv_bytes=d.s_eff)
    return out


def _assert_walk_parity(name, r, n, seed, *, tight=False, streamed=False,
                        backend=None, state=False):
    cands, view = _pool(n, seed, tight)
    items, H = _cohort(r, n, seed, streamed, state)
    kw = {"backend": backend} if backend else {}
    results, state = [], []
    for runner in (_run_sequential, _run_cohort):
        cv = ClusterView.from_candidates(cands, tier_fn=view.tier_of)
        sched = make_scheduler(name, H100_TP4_ITER, 64, seed=seed, **kw)
        assert supports_cohort(sched)
        infl = SelfContentionTracker()
        results.append(runner(sched, cv, view, items, H, infl))
        state.append((
            sched._rng.bit_generator.state,          # tie-break stream
            getattr(sched, "_next", None),           # rr cursor
            dict(infl._counts),                      # self-contention
            cv.free_memory[: cv.n].tolist(),         # reserved memory
        ))
    seq, coh = results
    assert seq == coh, f"{name}: decisions diverge"
    assert state[0] == state[1], f"{name}: scheduler/view state diverges"


class TestCohortWalkParity:
    @pytest.mark.parametrize("state", [False, True])
    @pytest.mark.parametrize("name", LADDER8)
    def test_serial_cohort(self, name, state):
        _assert_walk_parity(name, r=9, n=48, seed=1, state=state)

    @pytest.mark.parametrize("state", [False, True])
    @pytest.mark.parametrize("name", ["netkv-full", "netkv-pred"])
    def test_streamed_cohort(self, name, state):
        _assert_walk_parity(name, r=9, n=48, seed=2, streamed=True,
                            state=state)

    @pytest.mark.parametrize("name", LADDER8)
    def test_tight_memory_none_rows(self, name):
        # Infeasible rows return None and must not draw from the RNG.
        _assert_walk_parity(name, r=12, n=16, seed=3, tight=True)

    def test_singleton_cohort(self):
        _assert_walk_parity("netkv-full", r=1, n=48, seed=4)

    def test_rejects_unsupported_scheduler(self):
        from repro.core.batch_assign import NetKVBatch

        sched = NetKVBatch(H100_TP4_ITER, 64)
        assert not supports_cohort(sched)

    @given(data=st.data())
    @settings(max_examples=30, deadline=None)
    def test_fuzz_cohort_composition(self, data):
        name = data.draw(st.sampled_from(LADDER8))
        r = data.draw(st.integers(min_value=1, max_value=10))
        n = data.draw(st.integers(min_value=4, max_value=40))
        seed = data.draw(st.integers(min_value=0, max_value=2**20))
        tight = data.draw(st.booleans())
        streamed = data.draw(st.booleans())
        _assert_walk_parity(name, r, n, seed, tight=tight, streamed=streamed)


# --------------------------------------------------------------------------
# kernel layer
# --------------------------------------------------------------------------
def _kernel_args(r: int, n: int, seed: int, state: bool = False):
    """Scorer inputs; with ``state`` every row's s_r also holds a share of
    fixed state (``state_bytes``), drawn from a generator of its own so the
    other inputs are the same draws either way."""
    rng = np.random.default_rng(seed)
    pool = dict(
        free_mem=rng.uniform(1e9, 4e11, n).astype(np.float32),
        queued=rng.integers(0, 8, n).astype(np.float32),
        batch=rng.integers(0, 64, n).astype(np.float32),
        healthy=(rng.random(n) > 0.1).astype(np.float32),
        iter_scale=rng.uniform(1.0, 2.0, n).astype(np.float32),
    )
    lens = rng.integers(256, 8192, r)
    rows = dict(
        hit_rows=(rng.uniform(0, 1.2, (r, n)) * lens[:, None]).astype(
            np.float32),
        tier_rows=rng.integers(0, 4, (r, n)).astype(np.int32),
        infl_rows=rng.integers(0, 5, (r, 4)).astype(np.float32),
        s_r=[float(l) * 320 * 1024 for l in lens],
        input_len=[float(l) for l in lens],
    )
    scal = dict(
        tier_bw=[PAPER_TIER_BANDWIDTH[t] for t in range(4)],
        tier_lat=[PAPER_TIER_LATENCY[t] for t in range(4)],
        congestion=[0.1 * t for t in range(4)],
        iter_a=H100_TP4_ITER.a, iter_b=H100_TP4_ITER.b,
        m_min=2.0 * 1024**3, beta_max=64,
    )
    if state:
        share = np.random.default_rng(seed + 1000).uniform(0.05, 0.95, r)
        rows["state_bytes"] = [float(np.float32(f * s))
                               for f, s in zip(share, rows["s_r"])]
    return pool, rows, scal


class TestCohortKernel:
    def test_cohort_rows_match_single_row_kernel(self):
        from repro.kernels.netkv_score import netkv_score, netkv_score_cohort

        r, n = 5, 24
        pool, rows, scal = _kernel_args(r, n, seed=11)
        costs, best = netkv_score_cohort(
            **pool, **rows, **scal, interpret=True)
        costs = np.asarray(costs)
        best = np.asarray(best)
        for i in range(r):
            c1, b1 = netkv_score(
                pool["free_mem"], pool["queued"], pool["batch"],
                rows["hit_rows"][i], rows["tier_rows"][i], pool["healthy"],
                pool["iter_scale"], scal["tier_bw"], scal["tier_lat"],
                scal["congestion"], rows["infl_rows"][i],
                s_r=rows["s_r"][i], input_len=rows["input_len"][i],
                iter_a=scal["iter_a"], iter_b=scal["iter_b"],
                m_min=scal["m_min"], beta_max=scal["beta_max"],
                interpret=True)
            assert np.array_equal(costs[i], np.asarray(c1)), f"row {i}"
            assert int(best[i]) == int(b1), f"row {i} argmin"

    def test_numpy_twin_matches_kernel(self):
        from repro.kernels.netkv_score import netkv_score_cohort

        pool, rows, scal = _kernel_args(4, 24, seed=12)
        c_k, b_k = netkv_score_cohort(**pool, **rows, **scal, interpret=True)
        c_n, b_n = netkv_score_cohort(**pool, **rows, **scal, numpy=True)
        assert np.array_equal(np.asarray(c_k), np.asarray(c_n))
        assert np.array_equal(np.asarray(b_k), np.asarray(b_n))

    @pytest.mark.parametrize("state", [False, True])
    def test_pallas_backend_cohort_walk(self, state):
        # The pallas-backed CohortSelector precomputes serial rows through
        # the cohort-axis kernel; the walk must still match the sequential
        # pallas select stream exactly (shared XLA program).
        _assert_walk_parity("netkv-full", r=4, n=24, seed=5,
                            backend="pallas", state=state)

    def test_pallas_backend_mixed_streamed(self):
        # Streamed rows bypass the kernel inside one cohort; serial rows
        # around them must keep their precomputed kernel scores valid.
        _assert_walk_parity("netkv-full", r=5, n=24, seed=6, streamed=True,
                            backend="pallas")

    def test_feasibility_proof_needs_clearance(self):
        # The chip rounds the kernel's threshold differently from the host,
        # so a free value within the slack of the host's proves nothing and
        # the row is scored again.
        from repro.core.dispatch import _F32_SLACK, CohortSelector
        from repro.core.view import ROLE_DECODE

        class View:
            def __init__(self, free):
                self.cols = {"healthy": np.ones(2, bool),
                             "role": np.full(2, ROLE_DECODE),
                             "free_memory": np.array([free, 5e9])}

            def column(self, name):
                return self.cols[name]

        sel = CohortSelector.__new__(CohortSelector)
        thr = np.float32(3e9)
        sel._pl_thr32 = np.full((1, 2), thr)
        sel._pl_band = _F32_SLACK * (np.float32(1e9) + sel._pl_thr32)
        sel._free0, sel._healthy0 = np.array([5e9, 5e9]), np.ones(2, bool)
        band = float(sel._pl_band[0, 0])
        assert 256.0 < band < 1e4            # ulp(3e9) is 256
        for free, proven in ((5e9, True), (4e9, True), (2e9, False),
                             (thr + band / 2, False), (thr + 2 * band, True)):
            sel._cv = View(free)
            assert sel._pallas_feas_unchanged(0) is proven, free


def _compiles() -> int:
    """Backend compiles the span registry has seen, in any span or none."""
    from repro.profiling import REGISTRY

    return sum(len(ring) for ring in REGISTRY.backend.values())


def _score_cohort(pool, rows, scal, **kw):
    from repro.kernels.netkv_score import netkv_score_cohort

    c, b = netkv_score_cohort(**pool, **rows, **scal, interpret=True, **kw)
    return np.asarray(c), np.asarray(b)


class TestScorerProgram:
    """The scorer's compiled program is built once per (rows, lanes) shape
    and reused: request sizes, the iteration model, the cohort size up to
    two rows and the candidate count within one lane padding compile
    nothing new."""

    @pytest.mark.parametrize("field", ["s_r", "input_len", "iter_a", "iter_b",
                                       "m_min", "beta_max", "state_bytes"])
    def test_rows_and_scalars_share_one_program(self, field):
        from repro.kernels.netkv_score import _cohort_program

        pool, rows2, scal = _kernel_args(2, 20, seed=21,
                                         state=field == "state_bytes")
        rows1 = {k: v[:1] for k, v in rows2.items()}
        _score_cohort(pool, rows1, scal)
        built = _cohort_program.cache_info().currsize
        _score_cohort(pool, rows2, scal)       # R=2 runs R=1's program
        assert _cohort_program.cache_info().currsize == built
        n0 = _compiles()
        for k in (2.0, 3.0):
            for rows in (rows1, rows2):
                rows, sc = dict(rows), dict(scal)
                if field in rows:
                    rows[field] = [k * v for v in rows[field]]
                else:
                    sc[field] = type(sc[field])(k * sc[field])
                c, b = _score_cohort(pool, rows, sc)
                c_n, b_n = _score_cohort(pool, rows, sc, numpy=True)
                np.testing.assert_array_max_ulp(c, c_n, maxulp=1)
        assert _compiles() == n0
        assert _cohort_program.cache_info().currsize == built

    def test_candidate_counts_in_one_padding_share_one_program(self):
        from repro.kernels.netkv_score import BIG, _cohort_program, _prepare

        pool, rows, scal = _kernel_args(2, 24, seed=22)
        # Lanes 12-23 copy lanes 0-11 at half the iteration time, so each
        # is strictly cheaper than its twin: the D=24 winners lie past 12.
        for x in (*pool.values(), rows["hit_rows"], rows["tier_rows"]):
            x[..., 12:] = x[..., :12]
        pool["iter_scale"][12:] *= 0.5
        c24, b24 = _score_cohort(pool, rows, scal)
        assert (b24 >= 12).all()
        built = _cohort_program.cache_info().currsize
        cut = lambda x: {k: (v[..., :12] if isinstance(v, np.ndarray)
                             and v.shape[-1] == 24 else v)
                         for k, v in x.items()}
        c12, b12 = _score_cohort(cut(pool), cut(rows), scal)
        assert _cohort_program.cache_info().currsize == built
        assert c12.shape == (2, 12) and (b12 < 12).all()
        assert np.array_equal(c12, c24[:, :12])
        # The program masks by its candidate-count operand, not by what
        # the padding holds.
        rows_, lanes, flt, ints = _prepare(
            *[pool[k] for k in ("free_mem", "queued", "batch")],
            rows["hit_rows"], rows["tier_rows"], pool["healthy"],
            pool["iter_scale"], scal["tier_bw"], scal["tier_lat"],
            scal["congestion"], rows["infl_rows"], rows["s_r"],
            rows["input_len"], scal["iter_a"], scal["iter_b"], scal["m_min"],
            scal["beta_max"])
        ints[-1] = 12                          # the candidate count D
        costs, best = _cohort_program(rows_, lanes, True)(flt, ints)
        costs, best = np.asarray(costs)[:, 0], np.asarray(best)[:, 0, 0]
        assert np.array_equal(costs[:, :12], c12)
        assert (costs[:, 12:] == np.float32(BIG)).all()
        assert np.array_equal(best, b12)

    @pytest.mark.parametrize("state", [False, True])
    @pytest.mark.parametrize("r", [1, 2, 3, 4, 5])
    def test_cached_rows_match_numpy_twin(self, r, state):
        # Bit-for-bit on the data of test_numpy_twin_matches_kernel.  On
        # other draws the interpreter may fuse iter_a + iter_b * x into one
        # multiply-add and differ from the twin by an ulp; the winners agree.
        # Two such ulps can meet in Eq. (5)'s sum, with or without state:
        # over seeds 0-39 and R 1-5, 3 state-free and 2 state draws read two
        # ulps.  Seeds 0-7 hold one such state draw (seed 5, R=3).
        pool, rows, scal = _kernel_args(r, 24, seed=12, state=state)
        for _ in range(2):                     # built, then cached
            c_k, b_k = _score_cohort(pool, rows, scal)
            c_n, b_n = _score_cohort(pool, rows, scal, numpy=True)
            assert np.array_equal(c_k, c_n) and np.array_equal(b_k, b_n)
        for seed in range(8):
            pool, rows, scal = _kernel_args(r, 24, seed=seed, state=state)
            c_k, b_k = _score_cohort(pool, rows, scal)
            c_n, b_n = _score_cohort(pool, rows, scal, numpy=True)
            np.testing.assert_array_max_ulp(c_k, c_n, maxulp=2 if state else 1)
            assert np.array_equal(b_k, b_n), seed

    @pytest.mark.parametrize("numpy", [False, True])
    @pytest.mark.parametrize("r", [1, 2, 5])
    def test_zero_state_is_no_state(self, r, numpy):
        """Eq. (2') with S = 0 is Eq. (2) bit for bit, through the kernel
        and its NumPy twin; with state every row costs more where its
        prefix hit is deep, never less."""
        for seed in range(8):
            pool, rows, scal = _kernel_args(r, 130, seed=seed)
            c0, b0 = _score_cohort(pool, rows, scal, numpy=numpy)
            zero = dict(rows, state_bytes=[0.0] * r)
            c1, b1 = _score_cohort(pool, zero, scal, numpy=numpy)
            assert np.array_equal(c0, c1) and np.array_equal(b0, b1)
            pool, rows_s, scal = _kernel_args(r, 130, seed=seed, state=True)
            c2, _ = _score_cohort(pool, rows_s, scal, numpy=numpy)
            live = (c0 < 1e38) & (c2 < 1e38)
            assert (c2[live] >= c0[live]).all()


# --------------------------------------------------------------------------
# end-to-end layer: dispatch_mode="plane" vs "reference"
# --------------------------------------------------------------------------
GPU64 = dict(n_pods=2, racks_per_pod=2, servers_per_rack=2)       # 64 GPUs
GPU256 = dict(n_pods=2, racks_per_pod=8, servers_per_rack=2)      # 256 GPUs


def _burst_trace(bursts: int = 12, width: int = 4):
    """Same-arrival bursts whose prefills finish at the same instant on
    idle instances — the shape that actually forms serial dispatch
    cohorts (Poisson arrivals rarely collide at float timestamps)."""
    trace, rid = [], 0
    for b in range(bursts):
        t = 0.1 + 0.4 * b
        for i in range(width):
            hashes = tuple(f"b{b}-{i}-{j}" for j in range(8))
            trace.append(Request(rid, t, 1024, 64, hashes, rid, 1.0))
            rid += 1
    return trace


def _drive(mode: str, sched: str, trace, seed: int = 3, **kw):
    cfg = SimConfig(scheduler=sched, dispatch_mode=mode, warmup=0.5,
                    measure=4.0, seed=seed, **kw)
    sim = Simulation(cfg)
    sim.loop.trace_log = []
    sizes = []
    if mode == "plane":
        orig = sim._cohort_selector
        sim._cohort_selector = lambda items, reqs, now: (
            sizes.append(len(items)), orig(items, reqs, now))[1]
    sim.run(trace, drain=10.0)
    outs = [
        (rs.req.request_id, rs.prefill_instance, rs.decode_instance, rs.tier,
         rs.s_eff, rs.state_bytes, rs.rejected, rs.requeues, rs.prefill_end,
         rs.transfer_end, rs.first_token, rs.finish, rs.tokens_out,
         rs.hit_tokens, rs.sched_time)
        for rs in sim.records
    ]
    return outs, sim.loop.trace_log, sizes


def _assert_e2e_parity(sched: str, trace=None, min_cohort: int = 2,
                       seed: int = 3, **kw):
    trace = _burst_trace() if trace is None else trace
    o_p, l_p, sizes = _drive("plane", sched, trace, seed=seed, **kw)
    o_r, l_r, _ = _drive("reference", sched, trace, seed=seed, **kw)
    assert o_p == o_r, f"{sched}: outcomes diverge"
    assert l_p == l_r, f"{sched}: (time, lane) dispatch order diverges"
    # Guard against vacuous parity: the plane run must have actually
    # batched at least one multi-request cohort.
    assert sizes and max(sizes) >= min_cohort, \
        f"{sched}: no multi-request cohort formed (sizes={sizes[:8]}...)"


class TestDispatchModeParity:
    @pytest.mark.parametrize("sched", LADDER8)
    def test_64gpu_serial_bursts(self, sched):
        _assert_e2e_parity(sched, **GPU64)

    def test_64gpu_chunked_prefill(self):
        # Wider bursts stack several streams per prefill instance so
        # phase-3 (dispatch-ready) cohorts actually form.
        _assert_e2e_parity("netkv-full", trace=_burst_trace(8, 12),
                           **GPU64, chunk_tokens=512,
                           prefill_token_budget=1024)

    @pytest.mark.parametrize("kv", [None, HYBRID_KV], ids=["attn", "hybrid"])
    def test_64gpu_streamed_kv(self, kv):
        _assert_e2e_parity("netkv-full", trace=_burst_trace(8, 12),
                           **GPU64, chunk_tokens=512,
                           prefill_token_budget=1024, kv_streaming=True,
                           **({"kv_spec": kv} if kv else {}))

    @pytest.mark.parametrize("kv", [None, HYBRID_KV], ids=["attn", "hybrid"])
    def test_64gpu_faults_and_rewires(self, kv):
        faults = [FaultEvent(time=1.5, kind="kill_decode", instance_id=4),
                  FaultEvent(time=2.5, kind="add_decode")]
        rewires = [RewireEvent(time=2.0, scale={2: 0.25, 3: 0.25})]
        _assert_e2e_parity("netkv-full", **GPU64, faults=faults,
                           rewires=rewires, **({"kv_spec": kv} if kv else {}))

    def test_64gpu_reference_event_engine(self):
        # Cohorts must also form (and stay bit-exact) on the legacy heap
        # event engine — drain_due is implemented on both.
        _assert_e2e_parity("netkv-full", **GPU64, event_engine="reference")

    def test_256gpu_netkv_full(self):
        _assert_e2e_parity("netkv-full", trace=_burst_trace(10, 6),
                           **GPU256)

    def test_unsupported_scheduler_falls_back(self):
        # netkv-batch has no cohort path: plane mode silently degrades to
        # per-request dispatch and must equal reference exactly.
        trace = _burst_trace(6, 3)
        o_p, l_p, sizes = _drive("plane", "netkv-batch", trace, **GPU64)
        o_r, l_r, _ = _drive("reference", "netkv-batch", trace, **GPU64)
        assert o_p == o_r and l_p == l_r
        assert not sizes

    def test_invalid_dispatch_mode_rejected(self):
        with pytest.raises(ValueError):
            Simulation(SimConfig(scheduler="rr", dispatch_mode="bogus",
                                 **GPU64))
