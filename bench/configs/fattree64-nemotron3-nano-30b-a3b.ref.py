"""Plain references of NVIDIA-Nemotron-3-Nano-30B-A3B on the 64-GPU
fat-tree: the decode-instance score with the transfer of a hybrid
Mamba-2 / attention model, Eq. (2')-(7), the bytes a decision puts on the
fabric, and max-min fair sharing of the fabric's links (the base
deployment's ``waterfill``).

Bytes.  The attention layers' KV grows with the prompt (Eq. 1 over the
'*' layers of ``hybrid_override_pattern``); every Mamba-2 layer ('M')
adds its SSM state (heads x head dim x state size) and its conv state
(conv channels x (conv_kernel - 1)), whatever the prompt's length.  A
prefix hit on the decode instance covers attention KV only: the state at
the end of the prompt exists on the prefill instance alone and ships
whole.  So for one row and every candidate d

    s_r    = kv_per_token * l_r + S_r                                   (1)
    s_eff  = (s_r - S_r) * (1 - min(hit_d, l_r) / max(l_r, 1)) + S_r    (2')

with s_r and S_r from the published widths in the configuration beside
this file, never from the program's inputs; the rest of the score is the
base deployment's Eq. (3)-(7).  ``dtype`` is the precision every
operation is carried out in (float64 for the reference, bfloat16 for the
control).
"""

import functools
import json
import os

import numpy as np

from bench.harness import BENCH, load_module

_BASE = load_module(os.path.join(BENCH, "configs", "fattree64-internlm2-20b.ref.py"))
BIG, waterfill = _BASE.BIG, _BASE.waterfill

@functools.cache
def published_kv() -> dict:
    """The ``kv`` block of the configuration beside this file."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fattree64-nemotron3-nano-30b-a3b.json")) as f:
        return json.load(f)["kv"]


def kv_per_token(kv: dict) -> float:
    """Eq. (1)'s coefficient over the attention ('*') layers alone."""
    return (2.0 * kv["hybrid_override_pattern"].count("*")
            * kv["num_key_value_heads"] * kv["head_dim"] * kv["bytes_per_elem"])


def state_bytes(kv: dict) -> float:
    """SSM and conv state of every Mamba-2 layer."""
    inner = kv["mamba_num_heads"] * kv["mamba_head_dim"]
    ssm = inner * kv["ssm_state_size"]
    conv = (inner + 2 * kv["n_groups"] * kv["ssm_state_size"]) * (kv["conv_kernel"] - 1)
    return float(kv["hybrid_override_pattern"].count("M") * (ssm + conv)
                 * kv["state_bytes_per_elem"])


def fabric_bytes(kv: dict, input_len: float, hit_fraction: float) -> float:
    """Bytes a request of ``input_len`` tokens puts on the fabric when
    ``hit_fraction`` of its prompt is already on the chosen instance."""
    return kv_per_token(kv) * float(input_len) * (1.0 - hit_fraction) + state_bytes(kv)


def score(x: dict, dtype=np.float64):
    """Returns (costs (R, D), feasible (R, D)); infeasible costs are BIG."""
    def c(v):
        return np.asarray(v).astype(dtype)

    kv = published_kv()
    one = c(1.0)
    l64 = np.asarray(x["l_r"], np.float64)[:, None]
    s_fix = c(state_bytes(kv))
    s_r = c(kv_per_token(kv) * l64 + state_bytes(kv))
    l_r = c(l64)
    hit = np.minimum(c(x["hit"]), l_r)
    s_eff = (s_r - s_fix) * (one - hit / np.maximum(l_r, one)) + s_fix
    bw, cong, lat = c(x["bw"]), c(x["cong"]), c(x["lat"])
    infl = c(x["infl"])
    tier = np.asarray(x["tier"], np.int64)
    rows = np.arange(tier.shape[0])[:, None]
    b_eff = bw[tier] * (one - cong[tier]) / (one + infl[rows, tier])
    t_xfer = s_eff / b_eff + lat[tier]
    a, b = c(x["iter_a"]), c(x["iter_b"])
    batch, scale = c(x["batch"])[None, :], c(x["scale"])[None, :]
    t_iter = (a + b * batch) * scale
    blocked = np.maximum(c(0.0), c(x["queued"])[None, :] - (c(x["beta_max"]) - batch))
    t_queue = blocked * t_iter
    t_dec = (a + b * (batch + one)) * scale
    cost = (t_xfer + t_queue + t_dec).astype(np.float64)
    s_eff64 = s_eff.astype(np.float64)
    feasible = ((np.asarray(x["healthy"])[None, :] > 0.5)
                & (np.asarray(x["free"], np.float64)[None, :]
                   >= s_eff64 + float(x["m_min"])))
    return np.where(feasible, cost, BIG), feasible
