"""The scorer's host<->device round trip per call, in milliseconds: the
window's ``score.call`` (the program's launch) and ``score.readback`` (the
blocking read of its outputs) seconds over the ``score.call`` spans that
ended in the window.  Programs that read the outputs in the callers record
``score.readback`` there; it is summed the same way."""


def read(run):
    try:
        from repro.profiling import REGISTRY
    except ImportError:                    # a program without the registry
        return None
    calls = REGISTRY.ended("score.call", *run.window)
    if not calls:
        return None
    s = (REGISTRY.seconds("score.call", *run.window)
         + REGISTRY.seconds("score.readback", *run.window))
    return 1e3 * s / calls
