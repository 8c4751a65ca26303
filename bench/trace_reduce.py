"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation that ran, Pallas kernels among them.  A device's busy
time is the union of those events' intervals inside the window.  The
window and the host's doings come from the benchmark's own spans, which
the traced run writes into the trace as ``bench:<name>`` annotations on
the same clock.
"""

from __future__ import annotations

import dataclasses
import heapq
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"


def union_length(intervals) -> float:
    """Total length covered by possibly overlapping (start, end) pairs."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Op:
    name: str
    start: float      # seconds on the trace's clock
    end: float
    text: str         # name and string stats, for kernel matching


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    ops: dict[int, list[Op]]                 # device id -> ops in the window
    host: list[tuple[str, float, float]]     # (span name, start, end)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self, dev: int) -> float:
        return union_length((o.start, o.end) for o in self.ops.get(dev, ()))

    @property
    def busy_s_mean(self) -> float:
        if not self.ops:
            return 0.0
        return sum(self.busy_s(d) for d in self.ops) / len(self.ops)

    @property
    def busiest(self) -> int | None:
        return max(self.ops, key=self.busy_s) if self.ops else None

    def kernel(self, pattern: str, dev: int | None = None) -> list[Op]:
        """Ops whose name or string stats match ``pattern`` (a regex)."""
        rx = re.compile(pattern)
        devs = self.ops if dev is None else {dev: self.ops.get(dev, [])}
        return [o for d in devs for o in devs[d] if rx.search(o.text)]

    def kernel_s(self, pattern: str, dev: int | None = None) -> float:
        return sum(o.end - o.start for o in self.kernel(pattern, dev))

    def idle_by_host(self, dev: int) -> dict[str, float]:
        """Idle seconds of ``dev`` in the window, by the innermost
        benchmark span open on the host at the middle of each gap: the
        shortest span with start <= middle <= end, the first listed of
        equal length.  One sweep: gaps come in time order, spans are added
        as they start and dropped once they end before a gap's middle."""
        busy = merged((o.start, o.end) for o in self.ops.get(dev, ()))
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, min(s, hi)))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        spans = sorted((hs, i, he, name) for i, (name, hs, he)
                       in enumerate(self.host) if name != "window")
        open_spans: list[tuple[float, int, float, str]] = []   # heap by width
        k = 0
        out: dict[str, float] = {}
        for s, e in gaps:
            mid = 0.5 * (s + e)
            while k < len(spans) and spans[k][0] <= mid:
                hs, i, he, name = spans[k]
                heapq.heappush(open_spans, (he - hs, i, he, name))
                k += 1
            while open_spans and open_spans[0][2] < mid:
                heapq.heappop(open_spans)
            label = open_spans[0][3] if open_spans else "host"
            out[label] = out.get(label, 0.0) + (e - s)
        return out

    def breakdown(self) -> dict:
        dev = self.busiest
        by_op: dict[str, float] = {}
        for o in self.ops.get(dev, ()):
            by_op[o.name] = by_op.get(o.name, 0.0) + (o.end - o.start)
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
        idle = (sorted(self.idle_by_host(dev).items(), key=lambda kv: -kv[1])[:10]
                if dev is not None else [])
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _text(ev) -> str:
    parts = [ev.name]
    try:
        for k, v in ev.stats:
            if isinstance(v, str):
                parts.append(f"{k}={v}")
    except TypeError:
        pass
    return " ".join(parts)


def reduce_planes(planes, n_devices: int) -> Reduced:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each
    with ``name`` and ``events`` carrying ``name``, ``start_ns``,
    ``duration_ns`` and ``stats``), as ``jax.profiler.ProfileData`` gives."""
    host, dev_ops = [], {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if dev >= n_devices:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                dev_ops.setdefault(dev, []).extend(
                    Op(ev.name, ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9, _text(ev))
                    for ev in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name[len(HOST_PREFIX):],
                                     ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [(s, e) for n, s, e in host if n == "window"]
    if windows:
        lo, hi = windows[0]
    else:
        ts = [o.start for ops in dev_ops.values() for o in ops] + \
             [o.end for ops in dev_ops.values() for o in ops]
        lo, hi = (min(ts), max(ts)) if ts else (0.0, 0.0)
    ops = {d: [dataclasses.replace(o, start=max(o.start, lo), end=min(o.end, hi))
               for o in v if o.end > lo and o.start < hi]
           for d, v in dev_ops.items()}
    return Reduced((lo, hi), ops, host)


def reduce_file(path: str, n_devices: int) -> Reduced:
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes,
                         n_devices)
