"""The operation and byte counts against hand counts."""

from bench import roofline


def test_kv_bytes_eq1():
    # Eq. (1): 2 * layers * kv heads * head dim * bytes.
    smol = {"num_hidden_layers": 30, "num_key_value_heads": 3, "head_dim": 64,
            "bytes_per_elem": 2}
    assert roofline.kv_bytes_per_token(smol) == 2 * 30 * 3 * 64 * 2 == 23040
    internlm = {"num_hidden_layers": 48, "num_key_value_heads": 8,
                "head_dim": 128, "bytes_per_elem": 2}
    assert roofline.kv_bytes_per_token(internlm) == 196_608


def test_netkv_score_counts():
    assert roofline.netkv_score_bytes(1, 12) == 4 * (5 * 12 + 2 * 12 + 12)
    assert roofline.netkv_score_bytes(3, 128) == 4 * (5 * 128 + 9 * 128)
    assert roofline.netkv_score_flops(2, 12) == 34 * 24
