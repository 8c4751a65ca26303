"""Benchmark orchestrator: one harness per paper table/figure.

Usage:
    python -m benchmarks.run [--quick] [--only exp1,roofline] [--profile]

Prints one ``name,us_per_call,derived`` CSV line per harness (stdout
contract) and writes full tables to artifacts/bench/*.csv.  With
``--profile`` the event engines accumulate per-lane / per-handler
cumulative dispatch time across every simulation the selected harnesses
run, written to artifacts/bench/event_profile.csv — the first place to
look when hunting where event time goes.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from . import (
    decode_throughput,
    exp1_load_sweep,
    exp2_context_sweep,
    exp3_topology,
    exp4_staleness,
    exp5_prefix_sharing,
    exp6_ablation,
    exp7_scalability,
    exp8_beyond,
    exp9_extensions,
    exp10_chunked_prefill,
    exp11_scenario_sweep,
    exp12_deflection,
    net_throughput,
    roofline,
    sched_latency,
)

HARNESSES = {
    "exp1": exp1_load_sweep,       # Table II
    "exp2": exp2_context_sweep,    # Table III
    "exp3": exp3_topology,         # Fig. 1
    "exp4": exp4_staleness,        # Fig. 2
    "exp5": exp5_prefix_sharing,   # Fig. 3
    "exp6": exp6_ablation,         # Table IV / Fig. 4
    "exp7": exp7_scalability,      # Table V / Fig. 5
    "exp8": exp8_beyond,           # beyond-paper
    "exp9": exp9_extensions,       # beyond-paper: TopoPlane (multi-NIC + OCS rewire)
    "exp10": exp10_chunked_prefill,  # beyond-paper: ChunkPlane (chunked prefill + streamed KV)
    "exp11": exp11_scenario_sweep,   # beyond-paper: ScenarioPlane batched what-if sweeps
    "exp12": exp12_deflection,       # beyond-paper: RolePlane (deflection + P:D flips)
    "sched_latency": sched_latency,
    "net_throughput": net_throughput,      # FlowPlane vs reference engine
    "decode_throughput": decode_throughput,  # InstancePlane vs reference
    "roofline": roofline,          # §Roofline (reads dry-run artifacts)
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated harness names")
    ap.add_argument("--profile", action="store_true",
                    help="write per-lane/per-handler event dispatch self "
                         "times and per-span rows to "
                         "artifacts/bench/event_profile.csv")
    ap.add_argument("--trace", action="store_true",
                    help="record TracePlane spans + decision forensics in "
                         "every simulation the selected harnesses run; "
                         "writes artifacts/bench/trace.json (Perfetto) and "
                         "artifacts/bench/ttft_breakdown.csv")
    args = ap.parse_args()
    from repro.core.jaxutil import use_compile_cache

    use_compile_cache()
    if args.profile:
        from repro.profiling import enable_profiling
        enable_profiling(True)
    if args.trace:
        from repro.sim import enable_tracing
        enable_tracing(True)
    names = list(HARNESSES) if not args.only else args.only.split(",")
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod = HARNESSES[name]
        t0 = time.time()
        try:
            mod.main(quick=args.quick)
        except Exception as e:  # keep the suite going; report at the end
            failures += 1
            print(f"{name},{(time.time()-t0)*1e6:.0f},ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if args.profile:
        from repro.profiling import profile_rows

        from .common import write_csv
        rows = profile_rows()
        if rows:
            path = write_csv("event_profile", rows)
            print(f"# event profile: {len(rows)} (lane, handler) rows -> {path}",
                  file=sys.stderr)
    if args.trace:
        from repro.sim import trace as _trace

        from .common import OUT_DIR
        sess = _trace._SESSION
        if sess is not None and sess.n_runs:
            for path in sess.write(OUT_DIR):
                print(f"# trace: {sess.n_runs} runs -> {path}",
                      file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
