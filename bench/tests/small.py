"""Runs of the real cells' code at sizes a CPU test can hold, without the
harness's look for a chip."""

import json
import os
import types

from bench import harness
from bench.harness import BENCH, ROOT

# The simulator cell cut to a test's size: 2 s traces and a short drain;
# the deployment and every other key of the mix are the cell's.
SMALL_SIM_MIX = dict(duration=2.0, warm_duration=1.0, drain=10.0)


def cell_parts(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[name]
    config = harness.load_json(os.path.join(BENCH, "configs", cell["config"] + ".json"))
    mix = harness.load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    mix.update(SMALL_SIM_MIX)
    system = harness.load_module(os.path.join(BENCH, "systems", config["system"] + ".py"))
    return bench, cell, config, mix, system


def small_run(name, seed=12345678901234, seconds=2.0, config=None):
    """A run of cell ``name``; ``config`` puts another configuration of
    the same system in the cell's place."""
    import jax

    from bench.run import measure

    bench, cell, cell_config, mix, system = cell_parts(name)
    config = cell_config if config is None else config
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    run, metrics, device, _ = measure(bench, cell, config, mix, system,
                                      jax.devices()[:1], args, check_device=False)
    return run, system


def correct(run) -> bool:
    return bool(run.checks) and all(v <= lim for v, lim in run.checks.values())
