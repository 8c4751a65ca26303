"""Pallas TPU netkv_score: Algorithm 1's scoring loop as one fused kernel.

At 1000+ node scale the per-request scheduler scoring (lines 3-13 of
Alg. 1) runs over thousands of candidates; this kernel fuses Eq. (2)-(7)
elementwise math (Eq. (2') when part of the request's bytes is fixed
state) with the masked argmin reduction in a single VMEM pass.
Tier lookups use a one-hot contraction over the 4 tiers (no gather).

Candidates are padded to a multiple of 128 lanes; padding is masked
infeasible.  The iter model, m_min, beta_max and the real candidate count
ride SMEM scalar prefetch; the per-request (s_r, l_r, S_r) ride a rowed
block.
The inputs are packed on the host in NumPy and the compiled program is
built once per (rows, lanes) shape, so a warmed call compiles nothing.
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
                m_min: float, beta_max: int, state_bytes: float = 0.0,
                interpret: bool = False):
    """All candidate arrays are (D,).  Returns NumPy (costs (D,), best_idx
    ()).  ``state_bytes`` is the part of ``s_r`` that is fixed state, which
    no prefix hit discounts (Eq. 2').

    Single-row view of :func:`netkv_score_cohort` — one program serves both
    the sequential selector and the cohort dispatch path, which is what makes
    their costs bit-identical (two differently-shaped XLA programs are free
    to fuse/FMA differently; one shared program is not).  Row 0 is taken on
    the host: no device op runs after the program's one read.
    """
    with span("score.prepare"):
        hit_rows = np.asarray(hit_tokens, np.float32).reshape(1, -1)
        tier_rows = np.asarray(tier, np.int32).reshape(1, -1)
        infl_rows = np.asarray(n_inflight, np.float32).reshape(1, 4)
    costs, best = netkv_score_cohort(
        free_mem, queued, batch, hit_rows, tier_rows,
        healthy, iter_scale, tier_bw, tier_lat, congestion, infl_rows,
        s_r=[s_r], input_len=[input_len], iter_a=iter_a, iter_b=iter_b,
        m_min=m_min, beta_max=beta_max, state_bytes=[state_bytes],
        interpret=interpret,
    )
    return costs[0], best[0]


def _score_cohort_kernel(scal_ref, nreal_ref, free_ref, queued_ref, batch_ref,
                         hit_ref, tier_ref, healthy_ref, scale_ref, rscal_ref,
                         bw_ref, lat_ref, cong_ref, infl_ref, cost_ref, best_ref):
    """One grid step per cohort row: Eq. (2')-(7) + masked argmin, with the
    per-request scalars (s_r, l_r, S_r) riding a rowed block — row i is
    bit-identical to a single-row ``netkv_score`` call on the same snapshot.
    The per-row scalars deliberately arrive as a *block* rather than as
    ``scal_ref[base + program_id]``: a traced gather index changes XLA's
    fusion/FMA decisions for everything downstream, which costs bit-parity
    across cohort sizes (observed as 1-ulp cost drift off-TPU).  The real
    candidate count D rides scalar prefetch, so the program depends only on
    the padded shape.

    Rowed operands are ``(rows, 1, lanes)`` with ``(1, 1, lanes)`` blocks:
    Mosaic needs a block's last two dims to tile (8, 128) or to equal the
    array's, which a ``(1, lanes)`` block of an ``(rows, lanes)`` array does
    not.  Every constant is an explicit f32 so the kernel lowers the same
    whether or not ``jax_enable_x64`` is on."""
    f32 = jnp.float32
    one, zero = f32(1.0), f32(0.0)
    s_r = rscal_ref[0, 0, 0]
    l_r = rscal_ref[0, 0, 1]
    s_fix = rscal_ref[0, 0, 2]
    iter_a = scal_ref[0]
    iter_b = scal_ref[1]
    m_min = scal_ref[2]
    beta_max = scal_ref[3]

    hit = jnp.minimum(hit_ref[0], l_r)
    s_eff = (s_r - s_fix) * (one - hit / jnp.maximum(l_r, one)) + s_fix  # Eq. (2')

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
                & (lane < nreal_ref[0]))
    cost = jnp.where(feasible, cost, f32(BIG))
    cost_ref[0] = cost
    best_ref[0, 0, 0] = jax.lax.argmin(cost[0], 0, jnp.int32)


@functools.lru_cache(maxsize=None)
def _cohort_program(rows: int, lanes: int, interpret: bool):
    """The scorer's compiled program for ``rows`` cohort rows over ``lanes``
    padded candidates, built once per shape.  Its operands are the two
    buffers :func:`_prepare` packs on the host; slicing them apart happens
    inside the program.  The kernel's outputs, (rows, 1, lanes) f32 costs
    and (rows, 1, 1) int32 winners, leave it as one int32 buffer
    (:func:`_outputs` takes it apart), so the host reads a call in one
    transfer."""
    shared = pl.BlockSpec((1, lanes), lambda i, s, n: (_I0, _I0))
    row_blk = pl.BlockSpec((1, 1, lanes), lambda i, s, n: (i, _I0, _I0))
    row4 = pl.BlockSpec((1, 1, 4), lambda i, s, n: (i, _I0, _I0))
    tier4 = pl.BlockSpec((1, 4), lambda i, s, n: (_I0, _I0))
    score = pl.pallas_call(
        _score_cohort_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[shared, shared, shared, row_blk, row_blk, shared, shared,
                      row4, tier4, tier4, tier4, row4],
            out_specs=[
                row_blk,
                pl.BlockSpec((1, 1, 1), lambda i, s, n: (i, _I0, _I0),
                             memory_space=pltpu.SMEM),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, 1, lanes), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )

    # XLA names the custom call after the function it is traced in.  This
    # one keeps the name an eager call gets, which is how the kernel is
    # found in a device trace.
    def tpu_custom_call(flt, ints):
        pool, tiers, hit, tier, per_row, n_real = _operands(flt, ints, rows, lanes)
        costs, best = score(tiers[3], n_real, pool[0:1], pool[1:2], pool[2:3],
                            hit, tier, pool[3:4], pool[4:5], per_row[:, :, :4],
                            tiers[0:1], tiers[1:2], tiers[2:3], per_row[:, :, 4:])
        # The costs ride bit-cast to int32: winners bit-cast to f32 would be
        # denormals, which the chip may flush.
        return jnp.concatenate([
            best.reshape(rows),
            jax.lax.bitcast_convert_type(costs, jnp.int32).reshape(rows * lanes)])

    return jax.jit(tpu_custom_call)


def _operands(flt, ints, rows: int, lanes: int):
    """The program's operands as views of its two packed buffers, the f32
    ``flt`` and the int32 ``ints`` (NumPy views on the host, slices inside
    the program):

    * ``pool`` (5, lanes): free_mem, queued, batch, healthy, iter_scale;
    * ``tiers`` (4, 4): tier_bw, tier_lat, congestion, and the scalars
      (iter_a, iter_b, m_min, beta_max);
    * ``hit`` (rows, 1, lanes), and ``tier`` (rows, 1, lanes) in ``ints``;
    * ``per_row`` (rows, 1, 8): (s_r, input_len, state_bytes, 0) and the
      row's four in-flight counts;
    * ``n_real`` (1,) int32: D, the lanes that hold candidates.
    """
    n = rows * lanes
    pool_end = 5 * lanes
    hit_end = pool_end + n
    row_end = hit_end + 8 * rows
    return (flt[:pool_end].reshape(5, lanes),
            flt[row_end:row_end + 16].reshape(4, 4),
            flt[pool_end:hit_end].reshape(rows, 1, lanes),
            ints[:n].reshape(rows, 1, lanes),
            flt[hit_end:row_end].reshape(rows, 1, 8),
            ints[n:n + 1])


def _outputs(buf, rows: int, lanes: int):
    """(costs (rows, lanes) f32, best (rows,) int32): NumPy views of the
    program's output buffer read to the host."""
    return buf[rows:].view(np.float32).reshape(rows, lanes), buf[:rows]


def _prepare(free_mem, queued, batch, hit_rows, tier_rows, healthy, iter_scale,
             tier_bw, tier_lat, congestion, infl_rows, s_r, input_len, iter_a,
             iter_b, m_min, beta_max, state_bytes=0.0):
    """(rows, lanes, flt, ints): the program's shape and its two packed
    operand buffers (:func:`_operands`), filled on the host in NumPy: no
    device op runs, and a call moves two arrays.

    Lanes past D are zero (the kernel masks them).  An R=1 cohort becomes
    two identical rows: a grid of one step unrolls the body, and XLA fuses
    the unrolled program differently than the rows >= 2 grid loop
    (ulp-level cost drift), so every call, any cohort size and the
    single-row ``netkv_score`` wrapper, runs the same loop program.
    """
    f32 = np.float32
    hit_rows = np.asarray(hit_rows, f32)
    r, d = hit_rows.shape
    rows = max(r, 2)
    lanes = -(-d // LANES) * LANES
    flt = np.zeros((5 + rows) * lanes + 8 * rows + 16, f32)
    ints = np.zeros(rows * lanes + 1, np.int32)
    pool, tiers, hit, tier, per_row, n_real = _operands(flt, ints, rows, lanes)

    for k, col in enumerate((free_mem, queued, batch, healthy, iter_scale)):
        pool[k, :d] = np.asarray(col, f32)
    tiers[:] = np.asarray([tier_bw, tier_lat, congestion,
                           [iter_a, iter_b, m_min, float(beta_max)]], f32)
    hit[:r, 0, :d] = hit_rows
    tier[:r, 0, :d] = np.asarray(tier_rows, np.int32)
    per_row[:r, 0, 0] = np.asarray(s_r, f32).reshape(r)
    per_row[:r, 0, 1] = np.asarray(input_len, f32).reshape(r)
    per_row[:r, 0, 2] = np.asarray(state_bytes, f32).reshape(-1)
    per_row[:r, 0, 4:] = np.asarray(infl_rows, f32).reshape(r, 4)
    if r == 1:
        hit[1], tier[1], per_row[1] = hit[0], tier[0], per_row[0]
    n_real[0] = d
    return rows, lanes, flt, ints


def netkv_score_cohort(free_mem, queued, batch, hit_rows, tier_rows, healthy,
                       iter_scale, tier_bw, tier_lat, congestion, infl_rows,
                       *, s_r, input_len, iter_a: float, iter_b: float,
                       m_min: float, beta_max: int, state_bytes=0.0,
                       interpret: bool = False, numpy: bool = False):
    """Cohort-axis ``netkv_score``: R requests against one D-wide snapshot.

    Pool columns (free_mem/queued/batch/healthy/iter_scale) are (D,) and
    shared; ``hit_rows``/``tier_rows`` are (R, D) and ``infl_rows``/``s_r``/
    ``input_len``/``state_bytes`` are per-row (self-contention and KV size
    vary with the prefill source; a scalar ``state_bytes`` is every row's).
    Returns NumPy (costs (R, D), best (R,)) where row i matches a
    single-row ``netkv_score`` call bit-for-bit (same f32 op sequence,
    grid-stepped over the cohort axis).  The inputs are packed on the host
    and scored by one compiled program per padded shape
    (:func:`_cohort_program`); a call at a shape seen before compiles
    nothing.  A call is one launch and one blocking read of the padded
    outputs, which are cut to (R, D) on the host, so indexing the result
    dispatches nothing.  ``numpy=True`` routes through the f32 NumPy twin —
    the fallback when no XLA backend is usable.
    """
    if numpy:
        return _netkv_score_cohort_np(
            free_mem, queued, batch, hit_rows, tier_rows, healthy, iter_scale,
            tier_bw, tier_lat, congestion, infl_rows, s_r=s_r,
            input_len=input_len, iter_a=iter_a, iter_b=iter_b, m_min=m_min,
            beta_max=beta_max, state_bytes=state_bytes)
    with span("score.prepare"):
        r, d = np.shape(hit_rows)
        rows, lanes, flt, ints = _prepare(
            free_mem, queued, batch, hit_rows, tier_rows, healthy, iter_scale,
            tier_bw, tier_lat, congestion, infl_rows, s_r, input_len, iter_a,
            iter_b, m_min, beta_max, state_bytes)
    with span("score.call"):
        out = _cohort_program(rows, lanes, interpret)(flt, ints)
    with span("score.readback"):
        costs, best = _outputs(jax.device_get(out), rows, lanes)
    return costs[:r, :d], best[:r]


def s_eff_f32(s_r, state, hit, l_r):
    """The kernel's f32 Eq. (2') on the host, for (R, 1) ``s_r``, ``state``
    and ``l_r`` against (R, D) hits, rounded as the interpreted kernel
    rounds it: XLA:CPU contracts ``kv * miss + state`` into one multiply-add,
    and the product of two f32 is exact in f64, so one f64 add and a cast to
    f32 round as it does.  With zero state this is the f32 product
    ``s_r * miss``.  Mosaic on the TPU multiplies and adds apart and divides
    through a reciprocal, so the chip's values may differ by a few ulps."""
    f32 = np.float32
    hit = np.minimum(np.asarray(hit, f32), l_r)
    miss = f32(1.0) - hit / np.maximum(l_r, f32(1.0))
    return ((s_r - state).astype(np.float64) * miss + state).astype(f32)


def _netkv_score_cohort_np(free_mem, queued, batch, hit_rows, tier_rows,
                           healthy, iter_scale, tier_bw, tier_lat, congestion,
                           infl_rows, *, s_r, input_len, iter_a, iter_b,
                           m_min, beta_max, state_bytes=0.0):
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
    s_fix = np.asarray(state_bytes, f32).reshape(-1)[:, None]
    a, b = f32(iter_a), f32(iter_b)
    mm, bm = f32(m_min), f32(float(beta_max))

    s_eff = s_eff_f32(s_rv, s_fix, hit_rows, l_rv)
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
