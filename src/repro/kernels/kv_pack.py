"""Pallas TPU kv_pack: gather paged KV blocks into a contiguous DMA buffer.

FlowKV (cited by the paper as the transfer-mechanism optimisation) shows
that contiguous layout dominates per-transfer latency; on TPU the analogue
is packing the non-contiguous paged KV-cache blocks selected by the block
table into one contiguous HBM buffer so the prefill->decode transfer is a
single large DMA instead of per-page descriptors.

The block table rides scalar prefetch (SMEM); each grid step copies one
page through VMEM.  ``kv_unpack`` is the decode-side inverse (scatter).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block indices typed int32: a bare 0 turns int64 under jax_enable_x64,
# which Mosaic refuses in an index_map.
_I0 = np.int32(0)


def _pack_kernel(idx_ref, pool_ref, out_ref):
    # BlockSpec index_map already routed the right page into pool_ref.
    out_ref[...] = pool_ref[...]


def kv_pack(pool: jax.Array, block_table: jax.Array, *,
            interpret: bool = False) -> jax.Array:
    """pool: (n_pages, page_tokens, KV, dh); block_table: (n_sel,) int32.

    Returns (n_sel, page_tokens, KV, dh) — the selected pages, contiguous.
    """
    n_pages, page_tokens, kv, dh = pool.shape
    n_sel = block_table.shape[0]
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_sel,),
            in_specs=[
                pl.BlockSpec((1, page_tokens, kv, dh),
                             lambda i, idx: (idx[i], _I0, _I0, _I0)),
            ],
            out_specs=pl.BlockSpec((1, page_tokens, kv, dh),
                                   lambda i, idx: (i, _I0, _I0, _I0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_sel, page_tokens, kv, dh), pool.dtype),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), pool)


def _unpack_kernel(idx_ref, pool_ref, buf_ref, out_ref):
    del pool_ref  # aliased with out_ref; untouched pages keep pool contents
    out_ref[...] = buf_ref[...]


def kv_unpack(pool: jax.Array, buf: jax.Array, block_table: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """Inverse of kv_pack: scatter ``buf``'s pages into ``pool`` at the block
    table's page ids (in-place via input/output aliasing — the decode side
    receives the transfer buffer and lands it in freshly allocated pages)."""
    n_sel, page_tokens, kv, dh = buf.shape
    n_pages = pool.shape[0]
    return pl.pallas_call(
        _unpack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_sel,),
            in_specs=[
                pl.BlockSpec((1, page_tokens, kv, dh), lambda i, idx: (idx[i], _I0, _I0, _I0)),
                pl.BlockSpec((1, page_tokens, kv, dh), lambda i, idx: (i, _I0, _I0, _I0)),
            ],
            out_specs=pl.BlockSpec((1, page_tokens, kv, dh),
                                   lambda i, idx: (idx[i], _I0, _I0, _I0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pages, page_tokens, kv, dh), buf.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), pool, buf)
