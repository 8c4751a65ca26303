"""Plain references of the 64-GPU fat-tree deployment: NetKV's
decode-instance score, Eq. (2)-(7), the KV bytes a decision puts on the
fabric, Eq. (1)-(2), and max-min fair sharing of the fabric's links.

Score.

For one decision row and every candidate d:

    s_eff  = s_r * (1 - min(hit_d, l_r) / max(l_r, 1))                 (2)
    B_eff  = B_tau * (1 - cong_tau) / (1 + inflight_tau), tau = tier_d  (4)
    T_xfer = s_eff / B_eff + L_tau                                       (3)
    T_iter = (a + b * batch_d) * scale_d
    T_queue = max(0, queued_d - (beta_max - batch_d)) * T_iter           (6)
    T_dec  = (a + b * (batch_d + 1)) * scale_d                           (7)
    C_d    = T_xfer + T_queue + T_dec                                    (5)

feasible where healthy and free memory holds s_eff + m_min.  Written from
the paper's equations with NumPy alone; ``dtype`` is the precision every
operation is carried out in (float64 for the reference, bfloat16 for the
control that stands for a lower-precision scorer).

Bytes.  s_r = 2 * layers * KV heads * head dim * bytes * l_r (Eq. 1) and
the transfer moves s_eff of Eq. (2) for the instance chosen
(``fabric_bytes``, which the harness reads for every decision and for the
mix's capacity model).

Water-filling.  Every flow's rate grows at the same pace until a link it
crosses is full; the flows of the link that fills first are fixed at its
fair share, and the rest go on over what is left.  ``dtype`` is again the
precision of every operation (float64; float32 for the control).
"""

import numpy as np

BIG = 3.0e38


def score(x: dict, dtype=np.float64):
    """Returns (costs (R, D), feasible (R, D)); infeasible costs are BIG."""
    def c(v):
        return np.asarray(v).astype(dtype)

    one = c(1.0)
    l_r = c(x["l_r"])[:, None]
    s_r = c(x["s_r"])[:, None]
    hit = np.minimum(c(x["hit"]), l_r)
    s_eff = s_r * (one - hit / np.maximum(l_r, one))
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


def kv_bytes(kv: dict, input_len: float) -> float:
    """Eq. (1): K and V of every layer and KV head for ``input_len`` tokens."""
    return (2.0 * kv["num_hidden_layers"] * kv["num_key_value_heads"]
            * kv["head_dim"] * kv["bytes_per_elem"] * float(input_len))


def s_eff(s_r: float, hit: float, input_len: float) -> float:
    """Eq. (2): the bytes left to move after ``hit`` prefix tokens."""
    return s_r * (1.0 - min(hit, input_len) / max(input_len, 1.0))


def fabric_bytes(kv: dict, input_len: float, hit_fraction: float) -> float:
    """Bytes a request of ``input_len`` tokens puts on the fabric when
    ``hit_fraction`` of its prompt is already on the chosen instance."""
    return kv_bytes(kv, input_len) * (1.0 - hit_fraction)


def waterfill(paths, caps, dtype=np.float64):
    """Max-min fair rates.  ``paths`` (flows, hops) holds link ids, padded
    with the id ``len(caps) - 1``, whose capacity is infinite; ``caps``
    is every link's capacity."""
    paths = np.asarray(paths, np.int64)
    resid = np.asarray(caps).astype(dtype)
    pad = len(resid) - 1
    rates = np.zeros(len(paths), dtype)
    unfixed = np.ones(len(paths), bool)
    while unfixed.any():
        count = np.bincount(paths[unfixed].ravel(), minlength=len(resid))
        count[pad] = 0
        share = np.full(len(resid), np.inf, dtype)
        live = count > 0
        share[live] = resid[live] / count[live].astype(dtype)
        link = int(np.argmin(share))
        fix = unfixed & (paths == link).any(axis=1)
        rates[fix] = share[link]
        for row in paths[fix]:
            hops = row[row != pad]
            resid[hops] = np.maximum(resid[hops] - share[link], dtype(0))
        unfixed &= ~fix
    return rates
