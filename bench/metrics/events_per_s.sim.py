"""Events the simulator's event loop dispatched per second of window
(``loop.processed`` summed over the window's simulations)."""


def read(run):
    n = run.counters.get("events")
    return n / run.window_s if n else None
