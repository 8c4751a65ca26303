"""TracePlane: parity, zero-cost-off, forensics and exporter contracts.

The observability bar mirrors every prior plane's retirement bar: the
span set and every timestamp must be *bit-exact* across both event
engines (``event_engine="plane"`` / ``"reference"``) and both dispatch
modes (``dispatch_mode="plane"`` / ``"reference"``), and turning
tracing on must leave every simulated outcome untouched.
"""

from __future__ import annotations

import json
import math
import time

import pytest

from repro.core.cost import decision_breakdown
from repro.sim import (
    FaultEvent, RewireEvent, SimConfig, Simulation, TracePlane,
    enable_tracing, trace_session, ttft_breakdown_rows,
)
from repro.profiling import enable_profiling, profile_rows, span
from repro.sim.engine import make_event_loop
from repro.sim.trace import BREAKDOWN_COLUMNS, FORENSICS_COLUMNS
from repro.traces import generate_trace

GPU64 = dict(n_pods=2, racks_per_pod=2, servers_per_rack=2)       # 64 GPUs


def _drive(seed: int, cfg_kw: dict, rps: float = 45.0, *,
           scheduler: str = "netkv-full", trace: bool = True):
    tr = generate_trace("rag", duration=7.0, target_rps=rps, seed=seed)
    cfg = SimConfig(scheduler=scheduler, seed=seed, warmup=2.0,
                    measure=4.0, trace=trace, **cfg_kw)
    sim = Simulation(cfg)
    metrics = sim.run(tr, drain=25.0)
    return sim, metrics


def _all_modes(seed: int, cfg_kw: dict, rps: float = 45.0, **kw):
    out = {}
    for ee in ("plane", "reference"):
        for dm in ("plane", "reference"):
            sim, m = _drive(seed, dict(event_engine=ee, dispatch_mode=dm,
                                       **cfg_kw), rps, **kw)
            out[(ee, dm)] = (sim.trace.spans(), sim.trace.forensics_rows(), m)
    return out


def _assert_trace_parity(cfg_kw: dict, seed: int = 0, rps: float = 45.0,
                         **kw) -> None:
    drives = _all_modes(seed, cfg_kw, rps, **kw)
    spans0, dec0, m0 = drives[("plane", "plane")]
    assert spans0, "traced drive produced no spans"
    for key, (spans, dec, m) in drives.items():
        assert spans == spans0, f"span set diverges under {key}"
        assert dec == dec0, f"forensics rows diverge under {key}"
        assert m.ttft_mean == m0.ttft_mean


class TestTraceParity:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_64gpu_baseline(self, seed):
        _assert_trace_parity(dict(**GPU64, background=0.2), seed=seed)

    def test_64gpu_faults_rewires(self):
        faults = [
            FaultEvent(time=3.0, kind="kill_decode", instance_id=4),
            FaultEvent(time=3.5, kind="slowdown", instance_id=6, factor=1.5),
            FaultEvent(time=4.5, kind="add_decode"),
        ]
        rewires = [
            RewireEvent(time=3.2, scale={2: 0.25, 3: 0.25}),
            RewireEvent(time=5.0, scale={2: 4.0, 3: 4.0}),
        ]
        _assert_trace_parity(dict(**GPU64, background=0.15, faults=faults,
                                  rewires=rewires))

    def test_64gpu_streamed_kv(self):
        _assert_trace_parity(dict(**GPU64, background=0.1, chunk_tokens=512,
                                  prefill_token_budget=1024,
                                  kv_streaming=True))

    @pytest.mark.parametrize("scheduler", ["rr", "la", "ca", "cla"])
    def test_64gpu_ladder(self, scheduler):
        _assert_trace_parity(dict(**GPU64, background=0.2),
                             scheduler=scheduler)


class TestTraceOffIdentity:
    def test_tracing_changes_no_outcomes(self):
        cfg_kw = dict(**GPU64, background=0.2)
        s_off, m_off = _drive(0, dict(cfg_kw), trace=False)
        s_on, m_on = _drive(0, dict(cfg_kw), trace=True)
        assert s_off.trace is None
        assert m_off.ttft_mean == m_on.ttft_mean
        assert m_off.goodput_rps == m_on.goodput_rps
        off = [(rs.req.request_id, rs.first_token, rs.finish,
                rs.decode_instance) for rs in s_off.records]
        on = [(rs.req.request_id, rs.first_token, rs.finish,
               rs.decode_instance) for rs in s_on.records]
        assert off == on

    def test_untraced_metrics_still_attribute(self):
        # TTFT attribution derives from RequestState, so the new columns
        # are populated even without a TracePlane.
        _sim, m = _drive(0, dict(**GPU64, background=0.2), trace=False)
        assert math.isfinite(m.xfer_share_mean)
        assert math.isfinite(m.queue_wait_mean)
        assert 0.0 <= m.xfer_share_mean <= 1.0


class TestForensics:
    def test_stride_subsamples_deterministically(self):
        s1, _ = _drive(0, dict(**GPU64, background=0.2, trace_decisions=1))
        s4, _ = _drive(0, dict(**GPU64, background=0.2, trace_decisions=4))
        d1, d4 = s1.trace.forensics_rows(), s4.trace.forensics_rows()
        assert len(d1) > len(d4) > 0
        assert d4 == d1[::4]

    def test_winner_breakdown_recomputes(self):
        # Eq. (5) consistency on the recorded winner: cost = xfer + load
        # (load already bundles T_queue + T_decode), and decision_breakdown
        # terms are non-negative and finite.
        sim, _ = _drive(0, dict(**GPU64, background=0.2))
        rows = sim.trace.forensics_rows()
        assert rows
        for row in rows[:64]:
            r = dict(zip(FORENSICS_COLUMNS, row))
            # Attention-only KV: no fixed state beside the winner's s_eff.
            assert r["state_win"] == 0.0 and r["s_eff_win"] >= 0.0
            if r["kind"] != "netkv-full":
                continue
            assert r["cost_win"] == pytest.approx(
                r["xfer_win"] + r["load_win"], rel=1e-12)
            if not math.isnan(r["cost_run"]):
                assert r["cost_win"] <= r["cost_run"] or math.isclose(
                    r["cost_win"], r["cost_run"])

    def test_decision_breakdown_terms(self):
        from repro.core.cost import H100_TP4_ITER
        xfer, queue, first = decision_breakdown(
            s_eff=1e9, tier_bw=50e9, congestion=0.1, n_inflight=2,
            tier_latency=1e-4, q_d=3, beta_d=60, beta_max=64,
            iter_model=H100_TP4_ITER)
        assert xfer > 0 and queue == 0.0 and first > 0
        assert queue == 0.0  # 3 blocked <= 4 free slots


class TestSpans:
    def test_lifecycle_span_consistency(self):
        sim, _ = _drive(0, dict(**GPU64, background=0.2))
        by_kind: dict[str, int] = {}
        for kind, req, t0, t1, inst, tier, a, b in sim.trace.spans():
            by_kind[kind] = by_kind.get(kind, 0) + 1
            assert t1 >= t0, (kind, req)
        for needed in ("queue", "prefill", "xfer", "admit_wait",
                       "first_iter", "decode"):
            assert by_kind.get(needed, 0) > 0, f"missing {needed} spans"

    def test_xfer_segments_carry_bottleneck(self):
        sim, _ = _drive(0, dict(**GPU64, background=0.2))
        segs = [s for s in sim.trace.spans() if s[0] == "xfer_seg"]
        assert segs, "no transfer segments recorded"
        # Every non-degenerate segment names the water-fill bottleneck link.
        with_link = [s for s in segs if s[7] >= 0]
        assert with_link, "no bottleneck links recorded"

    def test_chunk_spans_telescope(self):
        sim, _ = _drive(0, dict(**GPU64, background=0.1, chunk_tokens=512,
                                prefill_token_budget=1024))
        done: dict[int, float] = {}
        takes: dict[int, float] = {}
        for kind, req, t0, t1, inst, tier, a, b in sim.trace.spans():
            if kind != "chunk":
                continue
            takes[req] = takes.get(req, 0.0) + a
            done[req] = max(done.get(req, 0.0), b)
        assert takes
        for req, total in takes.items():
            assert total == done[req], f"req {req}: takes don't telescope"


class TestExporters:
    def test_chrome_events_shape(self):
        sim, _ = _drive(0, dict(**GPU64, background=0.2))
        ev = sim.trace.to_chrome_events(pid=7, label="unit")
        json.dumps(ev)  # serialisable
        kinds = {e["ph"] for e in ev}
        assert "X" in kinds and "M" in kinds and "i" in kinds
        slices = [e for e in ev if e["ph"] == "X"]
        assert all(e["dur"] >= 0.0 and e["pid"] == 7 for e in slices)
        assert any(e["tid"] == 0 for e in ev if e["ph"] == "i")

    def test_breakdown_rows_schema(self):
        sim, _ = _drive(0, dict(**GPU64, background=0.2))
        rows = ttft_breakdown_rows(sim.records, run="unit")
        assert rows
        for row in rows[:16]:
            assert tuple(row) == BREAKDOWN_COLUMNS
            parts = [row["queue_wait"], row["prefill"], row["xfer"],
                     row["admit_wait"], row["first_iter"]]
            if all(not math.isnan(p) for p in parts):
                assert sum(parts) == pytest.approx(row["ttft"], rel=1e-9)

    def test_session_write(self, tmp_path):
        sess = enable_tracing()
        try:
            sess.context = "unit"
            _drive(0, dict(**GPU64, background=0.2), trace=False)
            assert sess.n_runs == 1  # session auto-enables the TracePlane
            paths = sess.write(tmp_path)
        finally:
            enable_tracing(False)
        jpath, cpath = paths
        with open(jpath) as fh:
            doc = json.load(fh)
        assert doc["traceEvents"]
        with open(cpath) as fh:
            header = fh.readline().strip().split(",")
        assert header == list(BREAKDOWN_COLUMNS)
        assert trace_session() is None

    def test_session_pause_suppresses_runs(self):
        sess = enable_tracing()
        try:
            sess.paused = True
            sim, _ = _drive(0, dict(**GPU64, background=0.2), trace=False)
            assert sim.trace is None and sess.n_runs == 0
        finally:
            enable_tracing(False)


class TestProfileSession:
    def test_sequential_runs_are_independent(self):
        # Regression: the module-global accumulator used to leak select()
        # time credit across runs — the second run's rows included the
        # first run's totals.
        totals = []
        for _ in range(2):
            sess = enable_profiling(True)
            _drive(0, dict(**GPU64, background=0.2), trace=False)
            rows = profile_rows()
            assert rows, "profiling produced no rows"
            # Decisions and water-fills are span rows, out of their handlers'.
            spans = {r["handler"] for r in rows if r["lane"] == "span"}
            assert {"select", "waterfill"} <= spans
            totals.append(sum(r["seconds"] for r in rows))
            assert rows == sess.profile_rows()
            enable_profiling(False)
        # Same drive twice: wall-clock noise aside, the second total must
        # be commensurate with the first, not cumulative (~2x).
        assert totals[1] < totals[0] * 1.7

    def test_loop_binds_session_at_construction(self):
        for kind in ("plane", "reference"):
            sess = enable_profiling(True)
            loop = make_event_loop(kind)
            assert loop.profile is sess
            enable_profiling(False)
            assert make_event_loop(kind).profile is None

            def handler(now):
                with span("select"):
                    time.sleep(0.005)

            loop.at(1.0, handler)
            loop.run()
            rows = {(r["lane"], r["handler"]): r for r in sess.profile_rows()}
            sel = rows[("span", "select")]
            own = rows[("generic", handler.__qualname__)]
            assert sel["events"] == own["events"] == 1
            # The handler's row is its self time: the span's 5 ms is debited.
            assert sel["seconds"] >= 0.005 > own["seconds"]
            assert profile_rows() == []  # no active session
