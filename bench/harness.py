"""What every cell shares: the run record, spans, compile counting, the
profiler window and the result line.

A system module (``bench/systems/<system>.py``) builds a cell's state,
drives its measured window and checks its outputs; a metric module
(``bench/metrics/<metric>.py``) reads one per-layer number from the
:class:`Run` the harness hands it.  Neither edits this file.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(RuntimeError):
    """The cell cannot run here; the run prints no result."""


def load_module(path: str):
    """Import a file by path (metric and reference files carry dots and
    dashes in their names, so they are not importable by dotted name)."""
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    name = "bench_" + os.path.basename(path).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile (numpy's default), NaN on no data."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class Spans:
    """Host-clock spans the benchmark's own files put around calls into
    the program's layers.  Off the traced run a span costs two clock reads;
    in the traced run it also writes a ``TraceAnnotation`` so the profiler
    trace holds it on the device events' clock."""

    def __init__(self):
        self.times: dict[str, list[tuple[float, float]]] = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.times.setdefault(name, []).append((t0, t1))

    def wrap(self, owner, attr: str, name: str):
        """Replace ``owner.attr`` by a spanned call; returns the original."""
        real = getattr(owner, attr)

        def spanned(*a, **kw):
            with self.span(name):
                return real(*a, **kw)

        setattr(owner, attr, spanned)
        return real

    def total(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> float:
        return sum(min(t1, hi) - max(t0, lo)
                   for t0, t1 in self.times.get(name, ()) if t1 > lo and t0 < hi)

    def durations(self, name: str) -> list[float]:
        return [t1 - t0 for t0, t1 in self.times.get(name, ())]


class CompileCounter:
    """Backend compiles (persistent-cache loads included) through
    ``jax.monitoring``."""

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


_COUNTER = None


def compile_counter() -> CompileCounter:
    """The process's one counter (listeners cannot be unregistered)."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


class Run:
    """What one run of one cell leaves for the metric readers."""

    def __init__(self, cell: dict, config: dict, mix: dict, seed: int,
                 seconds: float, trace: bool, devices: list):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.traced = seed, seconds, trace
        self.devices = devices
        self.spans = Spans()
        self.counters: dict[str, float] = {}
        self.samples: dict[str, list[float]] = {}
        self.window = (0.0, 0.0)          # perf_counter bounds
        self.device_trace = None          # trace_reduce.Reduced, traced runs
        self.e2e: dict[str, float] = {}
        self.checks: dict[str, tuple[float, float]] = {}
        self.attempted = 0
        self.failed = 0
        self.notes: dict[str, object] = {}

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def peaks(self) -> dict:
        from bench.peaks import peaks_for

        return peaks_for(self.devices[0].device_kind)


# ------------------------------------------------------------ profiler
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


@contextlib.contextmanager
def profiled(run: Run):
    """Profile the window when the run is traced (Python tracer off: the
    host side comes from the benchmark's own annotated spans)."""
    if not run.traced:
        yield
        return
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    run.spans.annotate = True
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        run.spans.annotate = False


def reduce_trace(run: Run) -> None:
    from bench import trace_reduce

    files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise BenchError("the profiler wrote no trace")
    run.device_trace = trace_reduce.reduce_file(
        max(files, key=os.path.getmtime), n_devices=run.cell["chips"])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def emit(run: Run, metrics: dict, device: dict, breakdown=None) -> None:
    """The check's numbers on stderr (last lines), then the result line."""
    ok = all(v <= lim for v, lim in run.checks.values()) and bool(run.checks)
    for name, (v, lim) in run.checks.items():
        print(f"check {name}: {v!r} limit {lim!r}", file=sys.stderr)
    out = {"correct": ok, "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
