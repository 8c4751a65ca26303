"""Share of the window's wall time spent selecting decode instances: the
program's ``select`` spans (``repro.profiling``), clipped to the
window, over the window."""


def read(run):
    try:
        from repro.profiling import REGISTRY
    except ImportError:                    # a program without the registry
        return None
    t = REGISTRY.seconds("select", *run.window)
    if not REGISTRY.ended("select", *run.window) or run.window_s <= 0:
        return None
    return 100.0 * t / run.window_s
