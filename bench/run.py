"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file, its traffic file and its per-layer
metric readers are all found by name from ``BENCHMARK.json``:
``bench/configs/<config>.json`` names the system module
(``bench/systems/<system>.py``) that builds and drives it, the traffic is
``bench/traffic/<traffic>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``.  The run fails, printing no result, when
JAX's first device is not a TPU or there are fewer chips than the cell
asks for.  ``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiled window.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cell_metrics(bench: dict, cell: dict, key: str, reported=None) -> list[dict]:
    """The metrics of ``key`` (end_to_end / per_layer) this cell reports:
    those listing it under ``workloads``, and those without the key whose
    ``moves`` (per-layer) the cell reports."""
    out = []
    for m in bench[key]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif key == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from bench.harness import BenchError

    # The compile cache lives in the checkout, at a fixed path (part of the
    # cache key), whatever the machine sets: the program takes it from here.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        return run_cell(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


def run_cell(args) -> int:
    from bench import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise harness.BenchError(f"no workload {args.workload!r}")
    cell = cells[args.workload]
    config = harness.load_json(os.path.join(harness.BENCH, "configs",
                                            cell["config"] + ".json"))
    mix = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         cell["traffic"] + ".json"))
    system = harness.load_module(os.path.join(
        harness.BENCH, "systems", config["system"] + ".py"))
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise harness.BenchError(f"no repro package under {src}")
    sys.path.insert(1, src)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise harness.BenchError(
            f"no TPU: JAX's first device is {devices[0].platform}")
    if len(devices) < cell["chips"]:
        raise harness.BenchError(
            f"{cell['name']} needs {cell['chips']} chips, found {len(devices)}")
    devices = devices[:cell["chips"]]
    return drive(bench, cell, config, mix, system, devices, args)


def drive(bench, cell, config, mix, system, devices, args) -> int:
    """Set up, measure, check and print the result line."""
    from bench import harness

    run, metrics, device, breakdown = measure(bench, cell, config, mix, system,
                                              devices, args)
    for k, v in sorted(run.counters.items()):
        print(f"count {k}: {v!r}", file=sys.stderr)
    harness.emit(run, metrics, device, breakdown)
    return 0


def measure(bench, cell, config, mix, system, devices, args,
            check_device: bool = True, t_start: float | None = None):
    """One run without its printing: (run, metrics, device, breakdown).
    Split from :func:`run_cell` so the control and the fault tests can
    drive runs without the look for a chip."""
    from bench import harness
    from repro.core.jaxutil import use_compile_cache

    use_compile_cache()
    counter = harness.compile_counter()
    run = harness.Run(cell, config, mix, args.seed, args.seconds,
                      bool(args.trace), devices)
    state = system.setup(run)
    setup_s = time.perf_counter() - (T_START if t_start is None else t_start)
    n0 = counter.n
    with harness.profiled(run):
        system.window(run, state)
    run.counters["compiles_in_window"] = counter.n - n0
    mem = harness.memory_peak(devices) if check_device else 0
    system.free(run, state)
    del state
    gc.collect()
    if run.traced:
        harness.reduce_trace(run)
    system.check(run)

    e2e = cell_metrics(bench, cell, "end_to_end")
    reported = {m["name"] for m in e2e}
    run.e2e["setup_s"] = setup_s
    if args.trace:
        metrics = {}
        for m in cell_metrics(bench, cell, "per_layer", reported):
            reader = harness.load_module(
                os.path.join(harness.BENCH, "metrics", m["name"] + ".py"))
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": run.e2e[m["name"]], "unit": m["unit"]}
                   for m in e2e if m["name"] in run.e2e}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    breakdown = None
    if run.traced and run.device_trace is not None:
        device["busy_s"] = run.device_trace.busy_s_mean
        device["window_s"] = run.device_trace.window_s
        breakdown = run.device_trace.breakdown()
    return run, metrics, device, breakdown


if __name__ == "__main__":
    sys.exit(main())
