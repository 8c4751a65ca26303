"""Backend compiles (persistent-cache loads included) per scorer call:
the backend compiles charged to the program's ``score.call`` span over the
``score.call`` spans, both ending in the window."""


def read(run):
    try:
        from repro.profiling import REGISTRY
    except ImportError:                    # a program without the registry
        return None
    calls = REGISTRY.ended("score.call", *run.window)
    if not calls:
        return None
    return REGISTRY.backend_compiles("score.call", *run.window) / calls
