"""Pallas TPU flash-decode: GQA single-token attention over a long KV cache.

The decode phase the NetKV scheduler feeds is memory-bandwidth bound: one
query token must stream the whole KV cache from HBM.  This kernel tiles the
cache into VMEM blocks of ``block_s`` positions, keeps the online-softmax
running statistics (m, l, acc) in VMEM scratch across the sequential grid
axis, and writes the normalised output on the last block — the TPU-native
analogue of flash-decoding (no warp shuffles: the within-block reduction
vectorises on the VPU/MXU, the across-block reduction rides the sequential
grid).

Layout: q is regrouped to (B, KV, G, dh) where G = H // KV query heads share
one KV head; the kernel processes one (batch, kv-head) pair per grid cell.
``pos`` (valid cache length) arrives via scalar prefetch in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, block_s: int, scale: float):
    sblk = pl.program_id(2)
    n_sblk = pl.num_programs(2)

    @pl.when(sblk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, dh)
    k = k_ref[0, :, 0, :].astype(jnp.float32)           # (block_s, dh)
    v = v_ref[0, :, 0, :].astype(jnp.float32)           # (block_s, dh)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G, S_blk)
    ids = sblk * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(ids < pos_ref[0], s, NEG_INF)

    m_prev = m_scr[...]                                  # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                               # (G, S_blk)
    alpha = jnp.exp(m_prev - m_new)                      # (G, 1)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(sblk == n_sblk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 pos: jax.Array, *, block_s: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q: (B, H, dh); k/v_cache: (B, S, KV, dh); pos: scalar valid length.

    Returns (B, H, dh).  H must be a multiple of KV (GQA grouping).
    """
    b, h, dh = q.shape
    _, s_max, kv, _ = k_cache.shape
    assert h % kv == 0, (h, kv)
    g = h // kv
    assert s_max % block_s == 0, (s_max, block_s)
    scale = dh ** -0.5
    qg = q.reshape(b, kv, g, dh)
    grid = (b, kv, s_max // block_s)

    kernel = functools.partial(_flash_decode_kernel, block_s=block_s, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, dh), lambda bi, ki, si, pos: (bi, ki, 0, 0)),
                pl.BlockSpec((1, block_s, 1, dh), lambda bi, ki, si, pos: (bi, si, ki, 0)),
                pl.BlockSpec((1, block_s, 1, dh), lambda bi, ki, si, pos: (bi, si, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, dh), lambda bi, ki, si, pos: (bi, ki, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), qg, k_cache, v_cache)
    return out.reshape(b, h, dh)
