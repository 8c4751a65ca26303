"""95th percentile of one decode-instance selection's host time, from the
simulator's own per-decision latencies (``Simulation.decision_latencies``)."""

from bench.harness import quantile


def read(run):
    xs = run.samples.get("select_s")
    return 1e3 * quantile(xs, 0.95) if xs else None
