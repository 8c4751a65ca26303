"""The deployment's plain references against hand counts."""

import os

import numpy as np
import pytest

from bench import harness
from bench.harness import BENCH

REF = harness.load_module(os.path.join(BENCH, "configs",
                                       "fattree64-internlm2-20b.ref.py"))
KV = {"num_hidden_layers": 48, "num_key_value_heads": 8, "head_dim": 128,
      "bytes_per_elem": 2}


def test_kv_bytes_eq1():
    # Eq. (1): 2 * layers * kv heads * head dim * bytes, per token.
    smol = {"num_hidden_layers": 30, "num_key_value_heads": 3, "head_dim": 64,
            "bytes_per_elem": 2}
    assert REF.kv_bytes(smol, 1) == 2 * 30 * 3 * 64 * 2 == 23040
    assert REF.kv_bytes(KV, 1) == 196_608


def test_fabric_bytes_is_eq1_eq2():
    for l, hit in ((1000.0, 250.0), (4096.0, 0.0), (1000.0, 5000.0), (0.0, 0.0)):
        frac = min(hit, l) / max(l, 1.0)
        assert REF.fabric_bytes(KV, l, frac) == REF.s_eff(REF.kv_bytes(KV, l), hit, l)


def test_eq1_eq2_bytes():
    assert REF.kv_bytes(KV, 1000) == 196_608_000.0
    assert REF.s_eff(1000.0, 250, 1000) == 750.0
    assert REF.s_eff(1000.0, 5000, 1000) == 0.0


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_waterfill_by_hand(dtype):
    # Links 0 (cap 10) and 1 (cap 4); link 2 is the infinite pad.
    # Flows: a over 0 and 1, b over 1, c over 0.  Link 1 fills first at
    # 2 each for a and b; c takes what link 0 has left, 8.
    paths = [[0, 1], [1, 2], [0, 2]]
    got = REF.waterfill(paths, [10.0, 4.0, np.inf], dtype)
    assert got.dtype == dtype
    assert np.allclose(got, [2.0, 2.0, 8.0])


def test_waterfill_is_max_min_fair():
    rng = np.random.default_rng(0)
    caps = np.append(rng.uniform(1, 10, 12), np.inf)
    paths = np.full((30, 4), 12)
    for f in range(30):
        hops = rng.choice(12, rng.integers(1, 5), replace=False)
        paths[f, :len(hops)] = hops
    rates = REF.waterfill(paths, caps)
    load = np.zeros(13)
    np.add.at(load, paths.ravel(), np.repeat(rates, 4))
    assert np.all(load[:12] <= caps[:12] * (1 + 1e-12))
    # Every flow crosses a full link on which no flow gets more than it.
    for f in range(30):
        hops = paths[f][paths[f] != 12]
        full = [l for l in hops if load[l] >= caps[l] * (1 - 1e-12)]
        assert any(rates[f] >= rates[(paths == l).any(axis=1)].max() * (1 - 1e-12)
                   for l in full)
