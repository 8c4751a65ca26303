"""Pallas TPU netkv_score: Algorithm 1's scoring loop as one fused kernel.

At 1000+ node scale the per-request scheduler scoring (lines 3-13 of
Alg. 1) runs over thousands of candidates; this kernel fuses Eq. (2)-(7)
elementwise math with the masked argmin reduction in a single VMEM pass.
Tier lookups use a one-hot contraction over the 4 tiers (no gather).

Candidates are padded to a multiple of 128 lanes; padding is masked
infeasible.  Scalars (s_r, l_r, iter model, m_min, beta_max) ride SMEM
scalar prefetch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.profiling import span

LANES = 128
BIG = 3.0e38
# Block indices typed int32: a bare 0 turns int64 under jax_enable_x64,
# which Mosaic refuses in an index_map.
_I0 = np.int32(0)


def netkv_score(free_mem, queued, batch, hit_tokens, tier, healthy, iter_scale,
                tier_bw, tier_lat, congestion, n_inflight,
                *, s_r: float, input_len: float, iter_a: float, iter_b: float,
                m_min: float, beta_max: int, interpret: bool = False):
    """All candidate arrays are (D,).  Returns (costs (D,), best_idx ()).

    Single-row view of :func:`netkv_score_cohort` — one program serves both
    the sequential selector and the cohort dispatch path, which is what makes
    their costs bit-identical (two differently-shaped XLA programs are free
    to fuse/FMA differently; one shared program is not).
    """
    with span("score.prepare"):
        hit_rows = jnp.asarray(hit_tokens, jnp.float32).reshape(1, -1)
        tier_rows = jnp.asarray(tier, jnp.int32).reshape(1, -1)
        infl_rows = jnp.asarray(n_inflight, jnp.float32).reshape(1, 4)
    costs, best = netkv_score_cohort(
        free_mem, queued, batch, hit_rows, tier_rows,
        healthy, iter_scale, tier_bw, tier_lat, congestion, infl_rows,
        s_r=[s_r], input_len=[input_len], iter_a=iter_a, iter_b=iter_b,
        m_min=m_min, beta_max=beta_max, interpret=interpret,
    )
    return costs[0], best[0]


def _score_cohort_kernel(scal_ref, free_ref, queued_ref, batch_ref, hit_ref,
                         tier_ref, healthy_ref, scale_ref, rscal_ref, bw_ref,
                         lat_ref, cong_ref, infl_ref, cost_ref, best_ref,
                         *, n_real: int):
    """One grid step per cohort row: Eq. (2)-(7) + masked argmin, with the
    per-request scalars (s_r, l_r) riding a rowed block — row i is
    bit-identical to a single-row ``netkv_score`` call on the same snapshot.
    The per-row scalars deliberately arrive as a *block* rather than as
    ``scal_ref[base + program_id]``: a traced gather index changes XLA's
    fusion/FMA decisions for everything downstream, which costs bit-parity
    across cohort sizes (observed as 1-ulp cost drift off-TPU).

    Rowed operands are ``(rows, 1, lanes)`` with ``(1, 1, lanes)`` blocks:
    Mosaic needs a block's last two dims to tile (8, 128) or to equal the
    array's, which a ``(1, lanes)`` block of an ``(rows, lanes)`` array does
    not.  Every constant is an explicit f32 so the kernel lowers the same
    whether or not ``jax_enable_x64`` is on."""
    f32 = jnp.float32
    one, zero = f32(1.0), f32(0.0)
    s_r = rscal_ref[0, 0, 0]
    l_r = rscal_ref[0, 0, 1]
    iter_a = scal_ref[0]
    iter_b = scal_ref[1]
    m_min = scal_ref[2]
    beta_max = scal_ref[3]

    hit = jnp.minimum(hit_ref[0], l_r)
    s_eff = s_r * (one - hit / jnp.maximum(l_r, one))                    # Eq. (2)

    tier = tier_ref[0]
    beff = jnp.zeros_like(s_eff)
    lat = jnp.zeros_like(s_eff)
    for t in range(4):
        sel = (tier == t).astype(f32)
        bt = bw_ref[0, t] * (one - cong_ref[0, t]) / (one + infl_ref[0, 0, t])  # Eq. (4)
        beff = beff + sel * bt
        lat = lat + sel * lat_ref[0, t]
    t_xfer = s_eff / jnp.maximum(beff, f32(1e-9)) + lat                  # Eq. (3)

    batch = batch_ref[...]
    scale = scale_ref[...]
    t_iter = (iter_a + iter_b * batch) * scale
    blocked = jnp.maximum(zero, queued_ref[...] - (beta_max - batch))
    t_queue = blocked * t_iter                                           # Eq. (6)
    t_dec = (iter_a + iter_b * (batch + one)) * scale                    # Eq. (7)

    cost = t_xfer + t_queue + t_dec                                      # Eq. (5)
    lane = jax.lax.broadcasted_iota(jnp.int32, cost.shape, 1)
    feasible = ((healthy_ref[...] > f32(0.5)) & (free_ref[...] >= s_eff + m_min)
                & (lane < jnp.int32(n_real)))
    cost = jnp.where(feasible, cost, f32(BIG))
    cost_ref[0] = cost
    best_ref[0, 0, 0] = jax.lax.argmin(cost[0], 0, jnp.int32)


def netkv_score_cohort(free_mem, queued, batch, hit_rows, tier_rows, healthy,
                       iter_scale, tier_bw, tier_lat, congestion, infl_rows,
                       *, s_r, input_len, iter_a: float, iter_b: float,
                       m_min: float, beta_max: int, interpret: bool = False,
                       numpy: bool = False):
    """Cohort-axis ``netkv_score``: R requests against one D-wide snapshot.

    Pool columns (free_mem/queued/batch/healthy/iter_scale) are (D,) and
    shared; ``hit_rows``/``tier_rows`` are (R, D) and ``infl_rows``/``s_r``/
    ``input_len`` are per-row (self-contention and KV size vary with the
    prefill source).  Returns (costs (R, D), best (R,)) where row i matches
    a single-row ``netkv_score`` call bit-for-bit (same f32 op sequence,
    grid-stepped over the cohort axis).  ``numpy=True`` routes through the
    f32 NumPy twin — the fallback when no XLA backend is usable.
    """
    if numpy:
        return _netkv_score_cohort_np(
            free_mem, queued, batch, hit_rows, tier_rows, healthy, iter_scale,
            tier_bw, tier_lat, congestion, infl_rows, s_r=s_r,
            input_len=input_len, iter_a=iter_a, iter_b=iter_b, m_min=m_min,
            beta_max=beta_max)
    with span("score.prepare"):
        r, d = hit_rows.shape[0], free_mem.shape[0]
        dp = -(-d // LANES) * LANES
        pad = dp - d

        hit_rows = jnp.asarray(hit_rows, jnp.float32)
        tier_rows = jnp.asarray(tier_rows, jnp.int32)
        infl_rows = jnp.asarray(infl_rows, jnp.float32).reshape(r, 4)
        s_rv = jnp.asarray(s_r, jnp.float32).reshape(r)
        l_rv = jnp.asarray(input_len, jnp.float32).reshape(r)
        rq = r
        if r == 1:
            # grid=(1,) unrolls the body and XLA fuses the unrolled program
            # differently than the r>=2 grid loop (ulp-level cost drift).  Pad
            # to two identical rows so every call — any cohort size, and the
            # single-row ``netkv_score`` wrapper — runs the same loop program.
            hit_rows = jnp.concatenate([hit_rows, hit_rows])
            tier_rows = jnp.concatenate([tier_rows, tier_rows])
            infl_rows = jnp.concatenate([infl_rows, infl_rows])
            s_rv = jnp.concatenate([s_rv, s_rv])
            l_rv = jnp.concatenate([l_rv, l_rv])
            r = 2

        def prep(x, dtype=jnp.float32):
            x = jnp.asarray(x, dtype)
            if pad:
                x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
            return x.reshape(-1, dp)

        def rowed(x, dtype=jnp.float32):
            return prep(x, dtype).reshape(r, 1, dp)

        f32 = jnp.float32
        scal = jnp.stack([jnp.asarray(v, f32) for v in
                          (iter_a, iter_b, m_min, float(beta_max))])
        rscal = jnp.stack([s_rv, l_rv, jnp.zeros(r, f32), jnp.zeros(r, f32)],
                          axis=1).reshape(r, 1, 4)
        args = (
            scal,
            prep(free_mem), prep(queued), prep(batch), rowed(hit_rows),
            rowed(tier_rows, jnp.int32), prep(healthy), prep(iter_scale), rscal,
            jnp.asarray(tier_bw, f32).reshape(1, 4),
            jnp.asarray(tier_lat, f32).reshape(1, 4),
            jnp.asarray(congestion, f32).reshape(1, 4),
            infl_rows.reshape(r, 1, 4),
        )
    with span("score.call"):
        kernel = functools.partial(_score_cohort_kernel, n_real=d)
        shared = pl.BlockSpec((1, dp), lambda i, s: (_I0, _I0))
        row_blk = pl.BlockSpec((1, 1, dp), lambda i, s: (i, _I0, _I0))
        row4 = pl.BlockSpec((1, 1, 4), lambda i, s: (i, _I0, _I0))
        costs, best = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(r,),
                in_specs=[shared, shared, shared, row_blk, row_blk, shared, shared,
                          row4]
                + [pl.BlockSpec((1, 4), lambda i, s: (_I0, _I0))] * 3 + [row4],
                out_specs=[
                    row_blk,
                    pl.BlockSpec((1, 1, 1), lambda i, s: (i, _I0, _I0),
                                 memory_space=pltpu.SMEM),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((r, 1, dp), f32),
                jax.ShapeDtypeStruct((r, 1, 1), jnp.int32),
            ],
            interpret=interpret,
        )(*args)
        return costs[:rq, 0, :d], best[:rq, 0, 0]


def _netkv_score_cohort_np(free_mem, queued, batch, hit_rows, tier_rows,
                           healthy, iter_scale, tier_bw, tier_lat, congestion,
                           infl_rows, *, s_r, input_len, iter_a, iter_b,
                           m_min, beta_max):
    """f32 NumPy twin of the cohort kernel (same op order, no XLA)."""
    f32 = np.float32
    d = free_mem.shape[0]
    free = np.asarray(free_mem, f32)[None, :]
    que = np.asarray(queued, f32)[None, :]
    bat = np.asarray(batch, f32)[None, :]
    hlt = np.asarray(healthy, f32)[None, :]
    scl = np.asarray(iter_scale, f32)[None, :]
    hit_rows = np.asarray(hit_rows, f32)
    tier = np.asarray(tier_rows, np.int32)
    bw = np.asarray(tier_bw, f32)
    lat4 = np.asarray(tier_lat, f32)
    cong = np.asarray(congestion, f32)
    infl = np.asarray(infl_rows, f32)
    s_rv = np.asarray(s_r, f32)[:, None]
    l_rv = np.asarray(input_len, f32)[:, None]
    a, b = f32(iter_a), f32(iter_b)
    mm, bm = f32(m_min), f32(float(beta_max))

    hit = np.minimum(hit_rows, l_rv)
    s_eff = s_rv * (f32(1.0) - hit / np.maximum(l_rv, f32(1.0)))
    beff = np.zeros_like(s_eff)
    lat = np.zeros_like(s_eff)
    for t in range(4):
        sel = (tier == t).astype(f32)
        bt = bw[t] * (f32(1.0) - cong[t]) / (f32(1.0) + infl[:, t:t + 1])
        beff = beff + sel * bt
        lat = lat + sel * lat4[t]
    t_xfer = s_eff / np.maximum(beff, f32(1e-9)) + lat
    t_iter = (a + b * bat) * scl
    blocked = np.maximum(f32(0.0), que - (bm - bat))
    t_queue = blocked * t_iter
    t_dec = (a + b * (bat + f32(1.0))) * scl
    cost = t_xfer + t_queue + t_dec
    feasible = (hlt > f32(0.5)) & (free >= s_eff + mm)
    cost = np.where(feasible, cost, f32(BIG))
    return cost[:, :d], np.argmin(cost, axis=1).astype(np.int32)
