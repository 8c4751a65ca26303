"""A configuration states its transfer shape with files of its own: the
program's spec from its ``kv_spec`` block, the bytes from its reference's
``fabric_bytes``.  The hybrid fixture beside this file is such a
configuration."""

import os

import numpy as np
import pytest

from bench import harness, traffic
from bench.harness import BENCH
from small import cell_parts, correct, small_run

SIM = "sim.fattree64.rag"
HERE = os.path.dirname(os.path.abspath(__file__))
SIM_PY = harness.load_module(os.path.join(BENCH, "systems", "sim.py"))
# sim.fattree64.rag's offered rate at the parent of the change that moved
# the bytes into the references, printed with repr.
RAG_RPS = 7.151023555890598


def hybrid():
    return harness.load_json(os.path.join(HERE, "hybrid_fixture.json"))


def test_hybrid_spec_is_from_its_published_widths():
    cfg = hybrid()
    ref = SIM_PY.reference(cfg)
    kv = cfg["kv"]
    assert ref.state_bytes(kv) == cfg["kv_spec"]["fixed_state_bytes"] == 49_930_240
    assert kv["hybrid_override_pattern"].count("*") == cfg["kv_spec"]["n_attn_layers"]
    spec = SIM_PY._sim_config(cfg, 1).kv_spec
    assert spec.kv_bytes_per_token == 6144 and spec.n_layers == 52
    for l, hit in ((4096, 0.0), (65536, 0.3), (1, 1.0)):
        assert ref.fabric_bytes(kv, l, hit) == pytest.approx(
            spec.kv_bytes(l) * (1.0 - hit), rel=1e-15)


def test_hybrid_fixture_runs_correct():
    run, _ = small_run(SIM, config=hybrid())
    assert run.counters["rows_checked"] > 0
    assert run.checks["bytes_mismatch"][0] == 0
    assert correct(run), run.checks


def test_hybrid_without_kv_spec_moves_other_bytes():
    cfg = hybrid()
    del cfg["kv_spec"]
    run, _ = small_run(SIM, config=cfg)
    assert run.checks["bytes_mismatch"][0] > 0
    assert not correct(run)


@pytest.mark.parametrize("key", ["n_attention_layers", "state_bytes", "name",
                                 "n_layers", "n_kv_heads", "d_head",
                                 "bytes_per_elem", "tp"])
def test_kv_spec_key_refused(key):
    cfg = hybrid()
    cfg["kv_spec"][key] = 1
    with pytest.raises(harness.BenchError, match=key):
        SIM_PY._sim_config(cfg, 1)


def test_rag_rate_is_the_parents():
    _, _, config, mix, system = cell_parts(SIM)
    win = traffic.MooncakeWindow(mix, system.deployment(config, system.reference(config)))
    assert win.rps == RAG_RPS


def _call(r, d, **extra):
    rng = np.random.default_rng(r)
    args = [rng.uniform(1e9, 1e11, d), np.zeros(d), rng.integers(0, 8, d),
            rng.uniform(0, 4096, (r, d)).astype(np.float32),
            rng.integers(0, 4, (r, d)).astype(np.int32), np.ones(d), np.ones(d),
            [1e10] * 4, [1e-5] * 4, [0.0] * 4, np.zeros((r, 4), np.float32)]
    kw = dict(s_r=[1e9] * r, input_len=[4096.0] * r, iter_a=0.0124, iter_b=1.6e-5,
              m_min=2e9, beta_max=64, interpret=True, **extra)
    out = (np.zeros((r, d), np.float32), np.zeros(r, np.int32))
    return args, kw, out


@pytest.mark.parametrize("r", [1, 3])
def test_kernel_rows_pass_every_keyword(r):
    state = np.arange(r, dtype=np.float32) * 7.0 + 1.0
    (inputs, costs, best), = SIM_PY.kernel_rows([_call(r, 12, state_bytes=state)])
    assert inputs["state_bytes"].dtype == np.float64
    assert inputs["state_bytes"].tolist() == state.tolist()
    assert inputs["s_r"].tolist() == [1e9] * r and inputs["l_r"].tolist() == [4096.0] * r
    assert "input_len" not in inputs
    assert inputs["iter_a"] == 0.0124 and inputs["beta_max"] == 64.0
    assert costs.shape == (r, 12) and best.shape == (r,)
    ref = SIM_PY.reference(cell_parts(SIM)[2])
    want, feasible = ref.score(inputs)
    assert want.shape == (r, 12) and feasible.shape == (r, 12)
