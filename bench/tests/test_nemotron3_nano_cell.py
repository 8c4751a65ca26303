"""The Nemotron-3-Nano cell: a hybrid transfer shape whose fixed Mamba-2
state no prefix hit discounts (Eq. 2'), held to its reference."""

import os
import shutil

import pytest

from bench import harness, traffic
from bench.harness import BENCH
from small import cell_parts, correct, small_run

CELL = "sim.fattree64.nemotron3-nano.rag"
CONFIG = "fattree64-nemotron3-nano-30b-a3b"
SIM_PY = harness.load_module(os.path.join(BENCH, "systems", "sim.py"))
# 0.8 of the capacity model's 115.04 req/s (prefill-bound), printed with repr.
RATE = 92.03131303741823


@pytest.fixture(scope="module")
def parts():
    return cell_parts(CELL)


@pytest.fixture(scope="module")
def run(parts):
    return small_run(CELL)[0]


def test_state_bytes_from_published_widths(parts):
    config = parts[2]
    ref = SIM_PY.reference(config)
    kv = config["kv"]
    assert ref.state_bytes(kv) == config["kv_spec"]["fixed_state_bytes"] == 49_930_240
    # 23 Mamba-2 layers x (SSM 64 x 64 x 128 + conv 6,144 channels x 3) x 4 B.
    assert ref.state_bytes(kv) == 23 * (64 * 64 * 128 + (64 * 64 + 2 * 8 * 128) * 3) * 4
    # The published config, copied whole, agrees with the widths used.
    for key in ("num_hidden_layers", "num_key_value_heads", "head_dim",
                "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "conv_kernel"):
        assert kv[key] == config[key], key
    spec = SIM_PY._sim_config(config, 1).kv_spec
    assert spec.kv_bytes_per_token == ref.kv_per_token(kv) == 6144
    for l, hit in ((4096, 0.0), (65536, 0.3), (1, 1.0)):
        want = 6144 * l * (1.0 - hit) + 49_930_240
        assert ref.fabric_bytes(kv, l, hit) == pytest.approx(want, rel=1e-15)
    assert ref.fabric_bytes(kv, 4096, 1.0) == 49_930_240


def test_offered_rate_is_pinned(parts):
    _, _, config, mix, system = parts
    win = traffic.MooncakeWindow(mix, system.deployment(config, system.reference(config)))
    assert win.rps == RATE


def test_cell_runs_correct(run):
    assert run.counters["rows_checked"] > 0
    assert run.checks["bytes_mismatch"][0] == 0
    assert correct(run), run.checks


def test_fabric_state_share_reads(run):
    reader = harness.load_module(os.path.join(BENCH, "metrics",
                                              "fabric_state_share.sim.py"))
    share = reader.read(run)
    assert share is not None and 0.0 < share < 100.0


def test_reference_that_discounts_the_state_disagrees(parts, tmp_path):
    """A copy of the reference with the program's old semantics, in which
    a prefix hit discounts the fixed state too, reads other bytes."""
    config = dict(parts[2])
    src = os.path.join(BENCH, "configs", CONFIG)
    shutil.copy(src + ".json", tmp_path / (CONFIG + ".json"))
    with open(src + ".ref.py") as f:
        text = f.read()
    for old, new in (
            ("s_eff = (s_r - s_fix) * (one - hit / np.maximum(l_r, one)) + s_fix",
             "s_eff = s_r * (one - hit / np.maximum(l_r, one))"),
            ("return kv_per_token(kv) * float(input_len) * (1.0 - hit_fraction) "
             "+ state_bytes(kv)",
             "return (kv_per_token(kv) * float(input_len) + state_bytes(kv)) "
             "* (1.0 - hit_fraction)")):
        assert text.count(old) == 1, old
        text = text.replace(old, new)
    (tmp_path / "discounting.ref.py").write_text(text)
    config["reference"] = str(tmp_path / "discounting.ref.py")
    run, _ = small_run(CELL, config=config)
    assert run.checks["bytes_mismatch"][0] > 0
    assert not correct(run)
