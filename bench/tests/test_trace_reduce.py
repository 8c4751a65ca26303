"""The trace-to-metric reduction on small traces: a hand-made one with a
device plane, and one recorded here through the profiler."""

import glob
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce


def ev(name, start_ns, dur_ns, **stats):
    return NS(name=name, start_ns=float(start_ns), duration_ns=float(dur_ns),
              stats=list(stats.items()))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def hand_trace():
    ms = 1_000_000
    dev0 = plane("/device:TPU:0", **{
        "XLA Ops": [ev("fusion.1", 10 * ms, 5 * ms),
                    ev("custom-call.2", 12 * ms, 6 * ms, long_name="netkv_score_cohort"),
                    ev("fusion.1", 40 * ms, 10 * ms),
                    ev("early", 0, 5 * ms)],        # before the window
        "XLA Modules": [ev("jit_step", 10 * ms, 40 * ms)]})
    dev1 = plane("/device:TPU:1", **{"XLA Ops": [ev("copy.3", 20 * ms, 2 * ms)]})
    dev5 = plane("/device:TPU:5", **{"XLA Ops": [ev("x", 20 * ms, 50 * ms)]})
    host = plane("/host:CPU", python=[
        ev("bench:window", 8 * ms, 92 * ms),        # window 8 .. 100 ms
        ev("bench:select", 20 * ms, 15 * ms),        # 20 .. 35 ms
        ev("other", 0, 1)])
    return [dev0, dev1, dev5, host]


def test_busy_idle_and_kernels():
    r = trace_reduce.reduce_planes(hand_trace(), n_devices=2)
    assert r.window == pytest.approx((0.008, 0.100))
    # TPU:0 busy 10..18 and 40..50 ms; the op before the window is cut away.
    assert r.busy_s(0) == pytest.approx(0.018)
    assert r.busy_s(1) == pytest.approx(0.002)
    assert r.busiest == 0
    assert r.busy_s_mean == pytest.approx(0.010)
    assert 5 not in r.ops                            # beyond the cell's chips
    assert r.kernel_s("netkv_score") == pytest.approx(0.006)
    assert r.kernel_s("^fusion") == pytest.approx(0.015)


def test_idle_gaps_by_host_span():
    r = trace_reduce.reduce_planes(hand_trace(), n_devices=2)
    idle = r.idle_by_host(0)
    # Gaps: 8..10 (host), 18..40 (mid 29: select), 50..100 (host).
    assert idle["select"] == pytest.approx(0.022)
    assert idle["host"] == pytest.approx(0.052)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s(0))
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.015)]
    assert b["idle_gaps"][0][0] == "host"


def test_union_length():
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.union_length([]) == 0


def test_recorded_trace_host_spans(tmp_path):
    """A trace written by the profiler here (CPU: no device plane) still
    yields the benchmark's window and spans on its clock."""
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:select"):
            jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    r = trace_reduce.reduce_file(path, n_devices=1)
    names = [n for n, _, _ in r.host]
    assert "window" in names and "select" in names
    assert r.window_s > 0
    (_, s0, e0), = [h for h in r.host if h[0] == "window"]
    (_, s1, e1), = [h for h in r.host if h[0] == "select"]
    assert s0 <= s1 <= e1 <= e0
    assert r.busiest is None and r.breakdown() == {"device_ops": [], "idle_gaps": []}


def test_scorer_roofline_reads_the_kernel_as_the_chip_names_it():
    """The op name below is the scorer's, as a v5e trace recorded it."""
    from types import SimpleNamespace

    from bench.harness import BENCH, load_module

    ms = 1_000_000
    name = ("%tpu_custom_call.1 = (f32[2,1,128]{2,1,0:T(1,128)}, "
            "s32[2,1,1]{2,1,0:T(1,128)S(1)}) custom-call(f32[4]{0:T(128)} %args_0_.1")
    dev = plane("/device:TPU:0", **{"XLA Ops": [
        ev(name, 10 * ms, 0.002 * ms), ev(name, 20 * ms, 0.002 * ms),
        ev("%copy.1 = f32[12]{0:T(128)} copy(f32[12]{0:T(128)} %args_0_.1)", 30 * ms, ms)]})
    host = plane("/host:CPU", python=[ev("bench:window", 0, 100 * ms)])
    r = trace_reduce.reduce_planes([dev, host], n_devices=1)
    reader = load_module(f"{BENCH}/metrics/netkv_score_roofline.sim.py")
    run = SimpleNamespace(device_trace=r, notes={"score_shapes": [(1, 12), (1, 12)]},
                          peaks=lambda: {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})
    share = reader.read(run)
    want = 100 * 2 * (4 * (5 * 12 + 3 * 12) / 819e9) / 4e-6
    assert share == pytest.approx(want)
    assert 0 < share < 100


def _idle_by_host_scan(r, dev):
    """The scan ``idle_by_host`` replaced: every host span for every gap."""
    busy = trace_reduce.merged((o.start, o.end) for o in r.ops.get(dev, ()))
    lo, hi = r.window
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    out = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        label, width = "host", float("inf")
        for name, hs, he in r.host:
            if hs <= mid <= he and he - hs < width and name != "window":
                label, width = name, he - hs
        out[label] = out.get(label, 0.0) + (e - s)
    return out


def _nested_trace(rng, n_top):
    """Simulations holding selections holding score calls, with water-fills
    between them; device ops inside and between the spans; equal-length
    twins and spans that end exactly on a gap's middle."""
    host, ops, t = [], [], 0.0
    names = ("select", "score.call", "score.readback", "waterfill")
    for _ in range(n_top):
        s0 = t
        t += rng.uniform(1e-4, 1e-3)
        for _ in range(int(rng.integers(1, 8))):
            name = names[int(rng.integers(len(names)))]
            a = t
            t += rng.uniform(1e-5, 5e-4)
            if rng.random() < 0.5:                   # a nested child
                c0 = rng.uniform(a, t)
                c1 = rng.uniform(c0, t)
                host.append(("score.prepare", c0, c1))
                if rng.random() < 0.2:               # an equal-length twin
                    host.append(("score.call", c0, c1))
            host.append((name, a, t))
            if rng.random() < 0.7:
                d0 = rng.uniform(a, t)
                ops.append(trace_reduce.Op("fusion", d0, d0 + rng.uniform(0, 1e-4), ""))
            t += rng.uniform(0, 2e-4)
        host.append(("simulation", s0, t))
        t += rng.uniform(0, 1e-3)
    host.append(("window", 0.0, t))
    rng.shuffle(host)
    return trace_reduce.Reduced((0.0, t), {0: ops}, host)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_idle_by_host_sweep_matches_the_scan(seed):
    import numpy as np

    r = _nested_trace(np.random.default_rng(seed), 400)
    assert len(r.host) > 2000 and len(r.ops[0]) > 1000
    want = _idle_by_host_scan(r, 0)
    assert r.idle_by_host(0) == want
    assert len(want) > 3



@pytest.mark.parametrize("edge", ["ends", "starts"])
def test_idle_by_host_span_on_a_gaps_middle(edge):
    """A span that ends, or starts, exactly on a gap's middle holds it."""
    r = trace_reduce.reduce_planes(hand_trace(), n_devices=2)
    busy = trace_reduce.merged((o.start, o.end) for o in r.ops[0])
    s, e = busy[0][1], busy[1][0]                   # the 18 .. 40 ms gap
    mid = 0.5 * (s + e)
    span = (s, mid) if edge == "ends" else (mid, mid + 1e-3)
    r.host.append((edge, *span))
    assert r.idle_by_host(0) == _idle_by_host_scan(r, 0)
    assert r.idle_by_host(0)[edge] == e - s
