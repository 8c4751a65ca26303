"""Operations and bytes the algorithms need, counted from their shapes.

Every roofline metric divides one of these by a measured time and a peak
from ``bench/peaks.py``.  The counts are what the algorithm needs,
not what an implementation happens to do: padding lanes, recomputation
and masked-out work do not count.
"""

from __future__ import annotations


def netkv_score_bytes(rows: int, d: int) -> int:
    """One ``netkv_score_cohort`` call over ``rows`` requests and ``d``
    candidates, f32/int32 (4 bytes): reads five shared (d,) pool columns,
    two (rows, d) rowed columns (hit tokens, tiers), writes the (rows, d)
    cost rows; per-row and per-tier scalars are noise and left out."""
    return 4 * (5 * d + 2 * rows * d + rows * d)


def netkv_score_flops(rows: int, d: int) -> int:
    """Eq. (2)-(7) per candidate: s_eff (3), tier select over 4 tiers of
    B_eff/latency (4 x 4), T_xfer (2), T_iter/T_queue/T_dec (8), the sum
    (2), the feasibility compare and the argmin (3): 34 operations."""
    return 34 * rows * d

