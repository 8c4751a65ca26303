"""The span registry (``repro.profiling``): nesting and self time,
bounded rings and window clipping, compile attribution, the profiler-clock
annotations, and the opt-in per-event lane rows."""

from __future__ import annotations

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import profiling
from repro.profiling import REGISTRY, enable_profiling, span
from repro.sim import SimConfig, Simulation
from repro.sim.engine import make_event_loop
from repro.traces import generate_trace

BACKEND = profiling.BACKEND_COMPILE
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
GPU64 = dict(n_pods=2, racks_per_pod=2, servers_per_rack=2)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(profiling, "_clock", c)
    REGISTRY.reset()
    yield c
    REGISTRY.reset()


def test_nesting_parent_and_self_time(clock):
    with span("outer") as a:
        clock.t = 1.0
        with span("inner") as b:
            clock.t = 3.0
        clock.t = 3.5
        with span("inner") as c:
            clock.t = 4.0
        clock.t = 5.0
    assert a.parent is None and b.parent is a and c.parent is a
    assert b.duration == 2.0 and c.duration == 0.5
    assert a.duration == 5.0 and a.self_seconds == pytest.approx(2.5)
    assert b.self_seconds == b.duration and c.self_seconds == c.duration
    assert REGISTRY.intervals("inner") == [(1.0, 3.0), (3.5, 4.0)]
    assert REGISTRY.ended("inner") == 2 and REGISTRY.seconds("inner") == 2.5
    assert REGISTRY.intervals("outer") == [(0.0, 5.0)]


def test_span_closes_on_exception(clock):
    with pytest.raises(ValueError):
        with span("boom"):
            clock.t = 1.0
            raise ValueError
    with span("after") as s:
        pass
    assert s.parent is None and REGISTRY.intervals("boom") == [(0.0, 1.0)]


def test_ring_is_bounded_and_clipped_to_a_window(clock, monkeypatch):
    monkeypatch.setattr(profiling, "RING", 4)
    for i in range(10):
        clock.t = 10.0 * i
        with span("tick"):
            clock.t = 10.0 * i + 2.0
    assert REGISTRY.intervals("tick") == [(60.0, 62.0), (70.0, 72.0),
                                          (80.0, 82.0), (90.0, 92.0)]
    # Clipped to [61, 81]: 1 + 2 + 1 seconds; two spans ended inside.
    assert REGISTRY.seconds("tick", 61.0, 81.0) == pytest.approx(4.0)
    assert REGISTRY.ended("tick", 61.0, 81.0) == 2
    assert REGISTRY.seconds("absent") == 0.0


def test_union_seconds_merges_overlaps_and_clips():
    xs = [(5.0, 6.0), (0.0, 2.0), (1.0, 3.0), (3.0, 4.0), (7.0, 7.0)]
    assert profiling.union_seconds(xs) == pytest.approx(5.0)
    assert profiling.union_seconds(xs, 1.5, 5.5) == pytest.approx(3.0)
    assert profiling.union_seconds(xs, 8.0, 9.0) == 0.0
    assert profiling.union_seconds([]) == 0.0


def test_compile_seconds_are_a_union_not_a_sum(clock):
    with span("outer"):
        clock.t = 1.0
        with span("inner"):
            clock.t = 5.0
            REGISTRY._on_duration(TRACE, 2.0)        # [3, 5]
            clock.t = 6.0
            REGISTRY._on_duration(LOWER, 4.0)        # [2, 6], holds [3, 5]
        clock.t = 10.0
        REGISTRY._on_duration(BACKEND, 3.0)          # [7, 10]
        REGISTRY._on_duration("/jax/compilation_cache/compile_time_saved_sec",
                              100.0)                 # not wall time
        clock.t = 11.0
    REGISTRY._on_duration(BACKEND, 0.5)              # no span open
    REGISTRY._on_duration("/jax/other/event", 1.0)   # not a compile event
    # Backend compiles are kept per innermost span, one interval each.
    assert list(REGISTRY.backend) == ["outer", None]
    assert list(REGISTRY.backend["outer"]) == [(7.0, 10.0)]
    assert set(REGISTRY.compile_under) == {"outer", "inner"}
    # Union: [2, 6] + [7, 10] = 7 s under outer (the sum would be 9), and
    # [2, 6] cut to inner's start at 1 s is still 4 s under inner.
    assert REGISTRY.compile_seconds("outer") == pytest.approx(7.0)
    assert REGISTRY.compile_seconds("inner") == pytest.approx(4.0)
    assert REGISTRY.compile_seconds("outer", 0.0, 8.0) == pytest.approx(5.0)
    assert REGISTRY.backend_compiles("outer", 0.0, 10.0) == 1
    assert REGISTRY.backend_compiles("outer", 0.0, 9.0) == 0
    assert REGISTRY.backend_compiles("inner") == 0


def _score_once(d=12, r=1):
    from repro.kernels.netkv_score import netkv_score_cohort
    from repro.kernels.ops import interpret_mode

    out = netkv_score_cohort(
        np.full(d, 1e12), np.zeros(d), np.zeros(d),
        np.zeros((r, d), np.float32), np.zeros((r, d), np.int32),
        np.ones(d), np.ones(d), [1e10] * 4, [1e-5] * 4, [0.0] * 4,
        np.zeros((r, 4), np.float32), s_r=[1e9] * r, input_len=[1024.0] * r,
        iter_a=0.0124, iter_b=1.6e-5, m_min=2e9, beta_max=64,
        interpret=interpret_mode())
    return int(np.asarray(out[1])[0])


def test_scorer_call_charges_its_backend_compile_once():
    r = 11                                           # a cohort size no other test scores
    calls0 = REGISTRY.ended("score.call")
    n0 = REGISTRY.backend_compiles("score.call")
    p0 = REGISTRY.backend_compiles("score.prepare")
    with span("test.select"):
        assert _score_once(r=r) == 0
    assert REGISTRY.ended("score.call") == calls0 + 1
    # The first call at a new shape compiles the program and the cut of its
    # padded outputs to (R, D), both charged to the innermost span; the host
    # preparation compiles nothing.
    assert REGISTRY.backend_compiles("score.call") == n0 + 2
    assert REGISTRY.backend_compiles("score.prepare") == p0
    (lo, hi), = REGISTRY.intervals("test.select")[-1:]
    assert 0 < REGISTRY.compile_seconds("test.select", lo, hi) <= hi - lo
    # The same shape again is one call of the cached program.
    with span("test.select"):
        assert _score_once(r=r) == 0
    assert REGISTRY.ended("score.call") == calls0 + 2
    assert REGISTRY.backend_compiles("score.call") == n0 + 2
    assert REGISTRY.backend_compiles("score.prepare") == p0
    (lo, hi), = REGISTRY.intervals("test.select")[-1:]
    assert REGISTRY.compile_seconds("test.select", lo, hi) == 0.0


def test_cached_jit_call_charges_nothing():
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.arange(8.0)
    f(x).block_until_ready()
    with span("test.cached"):
        f(x).block_until_ready()
    assert REGISTRY.backend_compiles("test.cached") == 0
    assert REGISTRY.compile_seconds("test.cached") == 0.0


def _small_sim(backend="numpy", duration=1.5, rps=6.0, seed=3):
    tr = generate_trace("rag", duration=duration, target_rps=rps, seed=seed)
    cfg = SimConfig(scheduler="netkv-full", scheduler_kwargs={"backend": backend},
                    seed=seed, warmup=0.0, measure=duration, **GPU64)
    sim = Simulation(cfg)
    sim.run(tr, drain=20.0)
    return sim


def test_decision_latencies_are_the_select_spans():
    REGISTRY.reset()
    sim = _small_sim()
    n = len(sim.decision_latencies)
    assert n > 0 and REGISTRY.ended("select") == n
    spans = REGISTRY.intervals("select")
    assert sim.decision_latencies == [e - s for s, e in spans]
    assert REGISTRY.ended("waterfill") > 0
    # No per-event lane spans unless profiling is on.
    assert not [k for k in REGISTRY.rings if k.startswith("loop.")]
    REGISTRY.reset()


def test_profiling_adds_lane_spans_and_rows():
    REGISTRY.reset()
    sess = enable_profiling(True)
    try:
        _small_sim()
    finally:
        enable_profiling(False)
    lanes = {k for k in REGISTRY.rings if k.startswith("loop.")}
    assert {"loop.arrival", "loop.net"} <= lanes
    rows = sess.profile_rows()
    by = {(r["lane"], r["handler"]): r for r in rows}
    assert by[("span", "select")]["events"] == REGISTRY.ended("select")
    # Rows are self times: together they are the time inside dispatches.
    dispatched = sum(e - s for k in lanes for s, e in REGISTRY.intervals(k))
    assert sum(r["seconds"] for r in rows) == pytest.approx(dispatched, rel=1e-6)
    REGISTRY.reset()


def _host_events(path, prefix):
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


def test_profiler_trace_holds_the_spans_on_its_clock(tmp_path):
    _score_once()                                    # warm the program
    REGISTRY.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test:outer"):
            _small_sim(backend="pallas", duration=1.0, rps=4.0)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    (_, lo, hi), = _host_events(path, "test:outer")
    events = _host_events(path, profiling.PREFIX)
    names = {n for n, _, _ in events}
    for name in ("select", "score.prepare", "score.call", "score.readback",
                 "waterfill"):
        assert profiling.PREFIX + name in names
    assert all(lo <= s <= e <= hi for _, s, e in events)
    selects = [(s, e) for n, s, e in events if n == "netkv:select"]
    for n, s, e in events:
        if n == "netkv:score.call":
            assert any(a <= s <= e <= b for a, b in selects)
    for name in ("select", "score.call", "waterfill"):
        traced = sorted((s, e) for n, s, e in events if n == profiling.PREFIX + name)
        kept = REGISTRY.intervals(name)
        assert len(traced) == len(kept) > 0
        for (ts, te), (ks, ke) in zip(traced, kept):
            assert abs((te - ts) - (ke - ks)) < 50e-6
    REGISTRY.reset()


def test_loop_binding_survives_reenable():
    first = enable_profiling(True)
    loop = make_event_loop("plane")
    second = enable_profiling(True)
    try:
        loop.at(0.5, lambda now: None)
        loop.run()
    finally:
        enable_profiling(False)
    assert loop.profile is first
    assert sum(r["events"] for r in first.profile_rows()) == 1
    assert second.profile_rows() == []
