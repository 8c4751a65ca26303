"""The traffic files repeat exactly, and every seed gets the same work."""

import json
import os

import numpy as np

from bench import harness, traffic
from bench.harness import BENCH


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def deployment():
    cfg = harness.load_json(os.path.join(BENCH, "configs",
                                         "fattree64-internlm2-20b.json"))
    sim = harness.load_module(os.path.join(BENCH, "systems", "sim.py"))
    return sim.deployment(cfg, sim.reference(cfg))


def test_mooncake_window_repeats():
    m = dict(mix("rag"), duration=5.0)
    a = traffic.MooncakeWindow(m, deployment())
    b = traffic.MooncakeWindow(m, deployment())
    assert a.rps == b.rps and 0 < a.rps < a.capacity_rps
    assert a.trace(2) == b.trace(2)
    assert a.trace(2) is a.trace(2)
    assert a.trace(1) != a.trace(2)
    ta = a.trace(2)
    assert len({h for r in ta for h in r.block_hashes if h[0] == "r"}) == \
        sum(len([h for h in r.block_hashes if h[0] == "r"]) for r in ta)


def test_small_seed_is_31_bits_and_repeats():
    big = 2**40 + 12345
    s = traffic.small_seed(big, 4, 0)
    assert s == traffic.small_seed(big, 4, 0) and 0 <= s < 2**31
    assert s != traffic.small_seed(big, 4, 1) != traffic.small_seed(7, 4, 1)


def test_mooncake_copy_matches_the_program_generator():
    """The copy is the program's generator at the commit it was taken
    from; this pins it to the program's output while the two agree."""
    from repro.traces.mooncake import PROFILES, generate_trace, profile_capacity

    prof = mix("rag")["profile"]
    mine = traffic.mooncake_trace(prof, duration=6.0, target_rps=3.0, seed=4)
    theirs = generate_trace("rag", duration=6.0, target_rps=3.0, seed=4)
    assert [(r.arrival, r.input_len, r.output_len, r.block_hashes) for r in mine] == \
           [(r.arrival, r.input_len, r.output_len, r.block_hashes) for r in theirs]
    dep = dict(deployment(),
               fabric_bytes=lambda l, hit: 327_680.0 * l * (1.0 - hit))
    got = traffic.mooncake_capacity(prof, **dep, **mix("rag")["capacity_model"])
    assert np.isclose(got, profile_capacity("rag"), rtol=1e-12)
    assert PROFILES["rag"].p_share == prof["p_share"]
