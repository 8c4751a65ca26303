"""Plain reference of a hybrid transfer shape (a test fixture): the
score and water-filling of the 64-GPU fat-tree deployment, and the bytes
a hybrid Mamba-2 / attention model puts on the fabric, from its published
widths.

Bytes.  The attention layers' KV grows with the prompt (Eq. 1 over the
'*' layers of ``hybrid_override_pattern``); every Mamba-2 layer ('M')
adds its SSM state (heads x head dim x state size) and its conv state
(conv channels x (conv_kernel - 1)), whatever the prompt's length.  As
the program moves it, a prefix hit discounts the whole of it (Eq. 2).
"""

import os

from bench.harness import BENCH, load_module

_BASE = load_module(os.path.join(BENCH, "configs", "fattree64-internlm2-20b.ref.py"))
BIG, score, waterfill = _BASE.BIG, _BASE.score, _BASE.waterfill


def state_bytes(kv: dict) -> float:
    """SSM and conv state of every Mamba-2 layer."""
    inner = kv["mamba_num_heads"] * kv["mamba_head_dim"]
    ssm = inner * kv["ssm_state_size"]
    conv = (inner + 2 * kv["n_groups"] * kv["ssm_state_size"]) * (kv["conv_kernel"] - 1)
    return float(kv["hybrid_override_pattern"].count("M") * (ssm + conv)
                 * kv["state_bytes_per_elem"])


def fabric_bytes(kv: dict, input_len: float, hit_fraction: float) -> float:
    per_token = (2.0 * kv["hybrid_override_pattern"].count("*")
                 * kv["num_key_value_heads"] * kv["head_dim"] * kv["bytes_per_elem"])
    return (per_token * float(input_len) + state_bytes(kv)) * (1.0 - hit_fraction)
