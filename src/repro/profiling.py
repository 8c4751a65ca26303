"""Wall-clock spans: one registry for every layer.

``with span("select"):`` around a layer's call records, on
``time.perf_counter``'s clock, the span's start and end, its parent (the
span open on this thread when it began) and its self time (its duration
less its children's).  Per span name the registry keeps, always on, the
most recent ``RING`` (start, end) intervals, so memory stays bounded in
long runs while a reader can still count and total the spans inside a
window of its own.

Compiles are charged to spans: one ``jax.monitoring`` listener,
registered once per process when the first span opens after JAX is
imported, sees each ``/jax/core/compile/*`` and
``/jax/compilation_cache/*`` duration event.  An event's interval ends
when the listener fires and starts its duration earlier; compile phases
nest (tracing runs inside lowering), so the registry keeps, per open span
name, the union of those intervals, never their sum.  Backend compiles
(persistent-cache loads included) are also kept per innermost span, one
interval each, so a reader can count them.

When a profiler session is active (``jax.profiler.start_trace``), every
span also writes a ``TraceAnnotation`` named ``netkv:<name>``, so the
program's host events sit on the device trace's clock.

Per-event lane spans (``loop.<lane>``, one per dispatched event) cost too
much to keep always on: ``enable_profiling()`` starts a ``ProfileSession``
that event loops bind when they are built.  Each span opened inside a
bound loop's dispatch credits its self time to that session, as a
(lane, handler) row for the dispatch and a ("span", name) row for a
program span; ``profile_rows()`` gives them as CSV-ready rows.

This module imports nothing of the package, so every layer can use it.
"""

from __future__ import annotations

import collections
import sys
import threading
import time

__all__ = ["BACKEND_COMPILE", "PREFIX", "RING", "REGISTRY", "ProfileSession",
           "Span", "current_session", "enable_profiling", "profile_rows",
           "span", "union_seconds"]

RING = 65536            # intervals kept per span name (and per compile key)
PREFIX = "netkv:"       # profiler-trace name prefix of every span
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_clock = time.perf_counter

# A compile event whose "duration" is not wall time spent at that moment.
_NOT_WALL = "/jax/compilation_cache/compile_time_saved_sec"
_COMPILE_PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")


class _Local(threading.local):
    def __init__(self) -> None:
        self.stack: list[Span] = []


_LOCAL = _Local()
_is_enabled = None      # TraceAnnotation.is_enabled once JAX is imported


def _annotating() -> bool:
    """True while a profiler session records host events.  JAX is never
    imported here: without it no session can be active."""
    f = _is_enabled
    if f is None:
        if "jax" not in sys.modules:
            return False
        f = _bind_jax()
    return f()


def _bind_jax():
    global _is_enabled
    import jax

    jax.monitoring.register_event_duration_secs_listener(REGISTRY._on_duration)
    _is_enabled = jax.profiler.TraceAnnotation.is_enabled
    return _is_enabled


def union_seconds(intervals, lo: float = float("-inf"),
                  hi: float = float("inf")) -> float:
    """Length of the union of (start, end) pairs, clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class Span:
    """One timed call.  ``duration`` and ``self_seconds`` are read after
    the ``with`` block."""

    __slots__ = ("name", "attrs", "t0", "t1", "parent", "child_s", "session",
                 "row", "_ann")

    def __init__(self, name: str, attrs=None, session=None, row=None) -> None:
        self.name = name
        self.attrs = attrs
        self.session = session
        self.row = row
        self.child_s = 0.0
        self.t0 = self.t1 = 0.0
        self._ann = None

    def __enter__(self) -> "Span":
        stack = _LOCAL.stack
        parent = stack[-1] if stack else None
        self.parent = parent
        if self.session is None and parent is not None:
            self.session = parent.session
        stack.append(self)
        if _annotating():
            import jax

            self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                     **(self.attrs or {}))
            self._ann.__enter__()
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = self.t1 = _clock()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        stack = _LOCAL.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        d = t1 - self.t0
        if self.parent is not None:
            self.parent.child_s += d
        REGISTRY._record(self.name, self.t0, t1)
        if self.session is not None:
            self.session.add(*(self.row or ("span", self.name)),
                             d - self.child_s)
        return False

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    @property
    def self_seconds(self) -> float:
        return self.t1 - self.t0 - self.child_s


class Registry:
    """The process's span intervals and the compiles charged to them."""

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        # span name -> ring of (start, end)
        self.rings: dict[str, collections.deque] = {}
        # innermost span name (None outside any) -> ring of backend compiles
        self.backend: dict = {}
        # span name -> ring of disjoint compile intervals while it was open
        self.compile_under: dict[str, collections.deque] = {}

    # ------------------------------------------------------------ record
    def _record(self, name: str, t0: float, t1: float) -> None:
        ring = self.rings.get(name)
        if ring is None:
            ring = self.rings[name] = collections.deque(maxlen=RING)
        ring.append((t0, t1))

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event.startswith(_COMPILE_PREFIXES) and event != _NOT_WALL:
            self._charge(event, duration)

    def _charge(self, event: str, duration: float) -> None:
        end = _clock()
        start = end - duration
        stack = _LOCAL.stack
        if event == BACKEND_COMPILE:
            key = stack[-1].name if stack else None
            ring = self.backend.get(key)
            if ring is None:
                ring = self.backend[key] = collections.deque(maxlen=RING)
            ring.append((start, end))
        seen = set()
        for frame in stack:                   # outermost first
            if frame.name in seen:
                continue
            seen.add(frame.name)
            under = self.compile_under.get(frame.name)
            if under is None:
                under = self.compile_under[frame.name] = collections.deque(
                    maxlen=RING)
            # Events fire at their end, in order: the new interval can only
            # overlap a suffix of the disjoint intervals kept so far.
            s = max(start, frame.t0)
            e = end
            while under and under[-1][1] >= s:
                ps, pe = under.pop()
                s, e = min(s, ps), max(e, pe)
            under.append((s, e))

    # -------------------------------------------------------------- read
    def intervals(self, name: str) -> list[tuple[float, float]]:
        return list(self.rings.get(name, ()))

    def seconds(self, name: str, lo: float = float("-inf"),
                hi: float = float("inf")) -> float:
        """Wall time covered by ``name``'s kept spans inside [lo, hi]."""
        return union_seconds(self.intervals(name), lo, hi)

    def ended(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> int:
        """Kept spans of ``name`` that ended inside [lo, hi]."""
        return sum(1 for _, e in self.intervals(name) if lo <= e <= hi)

    def compile_seconds(self, name: str, lo: float = float("-inf"),
                        hi: float = float("inf")) -> float:
        """Union of the compile intervals that fired while a ``name`` span
        was open (at any depth), inside [lo, hi]."""
        return union_seconds(self.compile_under.get(name, ()), lo, hi)

    def backend_compiles(self, name, lo: float = float("-inf"),
                         hi: float = float("inf")) -> int:
        """Backend compiles charged to ``name`` as the innermost span that
        ended inside [lo, hi]."""
        return sum(1 for _, e in self.backend.get(name, ()) if lo <= e <= hi)


REGISTRY = Registry()


def span(name: str) -> Span:
    """``with span(name):`` times the block (see the module docstring)."""
    return Span(name)


# ------------------------------------------------------------- profiling
class ProfileSession:
    """Per-lane / per-handler dispatch self time for one profiled run.

    Each event loop binds the session active at its construction, so
    back-to-back benchmark arms in one process each fill their own
    session.  ``rows``: (lane, handler) -> [count, self seconds]; a
    program span inside a dispatch (``select``, ``waterfill``, ...) takes
    its own ("span", name) row and is not in its handler's."""

    __slots__ = ("rows",)

    def __init__(self) -> None:
        self.rows: dict[tuple[str, str], list] = {}

    def add(self, lane: str, handler: str, dt: float) -> None:
        ent = self.rows.get((lane, handler))
        if ent is None:
            self.rows[(lane, handler)] = [1, dt]
        else:
            ent[0] += 1
            ent[1] += dt

    def dispatch(self, lane: str, handler: str) -> Span:
        """The ``loop.<lane>`` span around one event's handler."""
        return Span("loop." + lane, {"handler": handler}, self, (lane, handler))

    def profile_rows(self) -> list[dict]:
        rows = [
            dict(lane=lane, handler=handler, events=cnt, seconds=sec,
                 us_per_event=sec / cnt * 1e6 if cnt else 0.0)
            for (lane, handler), (cnt, sec) in self.rows.items()
        ]
        rows.sort(key=lambda r: -r["seconds"])
        return rows


# The session new loops bind (``benchmarks/run.py --profile`` enables one
# for the whole process; tests create scoped ones per run).
_CURRENT: ProfileSession | None = None


def enable_profiling(on: bool = True) -> ProfileSession | None:
    """Start a fresh process-wide ProfileSession (or stop profiling).

    Returns the new session; loops constructed while it is current bind
    it for their lifetime, so re-enabling mid-process starts clean totals
    without retroactively crediting already-running loops."""
    global _CURRENT
    _CURRENT = ProfileSession() if on else None
    return _CURRENT


def current_session() -> ProfileSession | None:
    return _CURRENT


def profile_rows() -> list[dict]:
    """Current session's dispatch profile as CSV-ready rows (slowest first)."""
    if _CURRENT is None:
        return []
    return _CURRENT.profile_rows()
