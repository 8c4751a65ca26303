"""NVIDIA-Nemotron-3-Nano-30B-A3B's transfer shape, for the tests that hold
every copy of Eq. (2') to it: 6 GQA layers of 2 KV heads x 128 in bf16
(6,144 B/token) beside 23 Mamba-2 layers' SSM and conv state in f32
(49,930,240 B a request), at TP4."""

from repro.core.cost import ModelKVSpec

PER_TOKEN = 2 * 6 * 2 * 128 * 2
STATE = 23 * (64 * 64 * 128 + (64 * 64 + 2 * 8 * 128) * 3) * 4
HYBRID_KV = ModelKVSpec("nemotron-3-nano-30b-a3b", n_layers=52, n_kv_heads=2,
                        d_head=128, n_attn_layers=6, fixed_state_bytes=STATE,
                        tp=4)
assert HYBRID_KV.kv_bytes_per_token == PER_TOKEN == 6144 and STATE == 49_930_240


def plain_bytes(input_len: int, hit_tokens: float) -> float:
    """Eq. (2') written out: the KV left after the hit, and the state whole."""
    frac = min(max(hit_tokens, 0.0), float(input_len)) / float(input_len)
    return PER_TOKEN * input_len * (1.0 - frac) + STATE
