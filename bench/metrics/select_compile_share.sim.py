"""Share of selection time spent compiling: the union of the JAX compile
and compile-cache event intervals that fired under the program's
``select`` spans, over the time in those spans, both clipped to the
window."""


def read(run):
    try:
        from repro.profiling import REGISTRY
    except ImportError:                    # a program without the registry
        return None
    t = REGISTRY.seconds("select", *run.window)
    if t <= 0:
        return None
    return 100.0 * REGISTRY.compile_seconds("select", *run.window) / t
