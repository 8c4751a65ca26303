"""The per-layer readers of the program's own spans and compile counts,
on a test-size run of the simulator cell."""

import math

import pytest

from bench.harness import BENCH, load_module
from bench.tests import small

READERS = ("select_share.sim", "select_compile_share.sim",
           "score_compiles_per_call.sim", "waterfill_span_share.sim")


@pytest.fixture(scope="module")
def read():
    run, _ = small.small_run("sim.fattree64.rag")
    return run, {name: load_module(f"{BENCH}/metrics/{name}.py").read(run)
                 for name in READERS}


def test_readers_find_the_program_spans(read):
    run, v = read
    assert all(v[name] is not None and math.isfinite(v[name]) for name in READERS), v
    assert 0 < v["select_share.sim"] < 100
    assert 0 <= v["select_compile_share.sim"] <= 100
    assert 0 < v["waterfill_span_share.sim"] < 100


def test_compiles_per_call_matches_the_harness_count(read):
    run, v = read
    # The scorer's compiles in the window, per call, account for every
    # backend compile there, give or take one (none once set-up has warmed
    # the scorer's shapes).
    calls = run.counters["kernel_calls"]
    assert calls > 0
    assert abs(v["score_compiles_per_call.sim"] * calls
               - run.counters["compiles_in_window"]) <= 1


def test_readers_skip_a_window_without_spans(read):
    run, _ = read
    import types

    empty = types.SimpleNamespace(window=(0.0, 1e-9), window_s=1e-9)
    for name in READERS:
        assert load_module(f"{BENCH}/metrics/{name}.py").read(empty) is None
    assert run.window_s > 0
