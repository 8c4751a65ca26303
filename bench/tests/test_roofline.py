"""The operation and byte counts against hand counts."""

from bench import roofline


def test_netkv_score_counts():
    assert roofline.netkv_score_bytes(1, 12) == 4 * (5 * 12 + 2 * 12 + 12)
    assert roofline.netkv_score_bytes(3, 128) == 4 * (5 * 128 + 9 * 128)
    assert roofline.netkv_score_flops(2, 12) == 34 * 24
