"""The reader of the scorer's round trip per call, on a test-size run of
the simulator cell."""

import math
import types

import pytest

from bench.harness import BENCH, load_module
from bench.tests import small

NAME = "score_roundtrip_ms.sim"


@pytest.fixture(scope="module")
def read():
    run, _ = small.small_run("sim.fattree64.rag")
    return run, load_module(f"{BENCH}/metrics/{NAME}.py").read(run)


def test_reader_finds_the_scorer_spans(read):
    _, v = read
    assert v is not None and math.isfinite(v) and v > 0


def test_scorer_round_trip_lies_inside_the_selection(read):
    run, v = read
    from repro.profiling import REGISTRY

    # Every scorer call is made while a decision is selected, so the round
    # trips of the window's calls add up to no more than its selections.
    calls = REGISTRY.ended("score.call", *run.window)
    assert calls == run.counters["kernel_calls"] > 0
    assert v * calls / 1e3 <= REGISTRY.seconds("select", *run.window)


def test_reader_skips_a_window_without_spans(read):
    run, _ = read
    empty = types.SimpleNamespace(window=(0.0, 1e-9), window_s=1e-9)
    assert load_module(f"{BENCH}/metrics/{NAME}.py").read(empty) is None
    assert run.window_s > 0
