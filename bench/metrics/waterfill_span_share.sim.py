"""Share of the window's wall time spent in FlowPlane's water-filling,
from the program's own ``waterfill`` span around
``FlowPlane._recompute_rates``, clipped to the window."""


def read(run):
    try:
        from repro.profiling import REGISTRY
    except ImportError:                    # a program without the registry
        return None
    if not REGISTRY.ended("waterfill", *run.window) or run.window_s <= 0:
        return None
    return 100.0 * REGISTRY.seconds("waterfill", *run.window) / run.window_s
