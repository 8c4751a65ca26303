"""Share of the window's wall time spent in FlowPlane's water-filling
(``FlowPlane._recompute_rates``), from the benchmark's span around it."""


def read(run):
    t = run.spans.total("waterfill", *run.window)
    return 100.0 * t / run.window_s if t > 0 else None
