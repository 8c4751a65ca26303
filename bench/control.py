"""Readings that set a cell's limits: the program on many seeds, and the
control (the plain reference in the next precision down, in the
program's place) on the same windows.  The benchmark's own runs never
run this.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

One process: each seed gets its own set-up, window and check, as a run
does.  One JSON line per seed: the numbers each check compares, for the
program and for the control.  Like a run, it refuses to read on anything
but a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import time

    import jax

    from bench import harness
    from bench.run import measure

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control: needs a TPU with the cell's chips", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    config = harness.load_json(os.path.join(harness.BENCH, "configs",
                                            cell["config"] + ".json"))
    mix = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         cell["traffic"] + ".json"))
    system = harness.load_module(os.path.join(harness.BENCH, "systems",
                                              config["system"] + ".py"))
    for seed in (int(s) for s in args.seeds.split(",")):
        a = types.SimpleNamespace(seed=seed, seconds=args.seconds, trace=0)
        t0 = time.perf_counter()
        run, metrics, _, _ = measure(bench, cell, config, mix, system, devices,
                                     a, t_start=t0)
        line = {"seed": seed,
                "program": {k: v for k, (v, _) in run.checks.items()},
                "control": system.control(run),
                "limits": {k: lim for k, (_, lim) in run.checks.items()},
                "metrics": {k: m["value"] for k, m in metrics.items()},
                "counters": run.counters}
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
