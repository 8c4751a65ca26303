"""KV transfer path: page the prefill cache, pack to a contiguous buffer.

The pack runs the Pallas ``kv_pack`` kernel (one contiguous buffer per
leaf, so the transfer is a single large copy) and the decode side lands it
with ``kv_unpack``: compiled by Mosaic on a TPU, in interpret mode on the
CPU (``kernels.ops.interpret_mode``).  The byte count it returns is what the
NetKV cost model prices (Eq. 1/2): callers skip packing the prefix-hit pages
(Eq. 2's lambda term).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.cost import B_TOK
from repro.kernels import ops


def paged_view(k_cache, page_tokens: int = B_TOK):
    """(P, 1, S, KV, dh) per-request cache leaf -> (P*S/page, page, KV, dh)."""
    p, b, s, kv, dh = k_cache.shape
    assert b == 1
    n_pages = s // page_tokens
    return k_cache.reshape(p * n_pages, page_tokens, kv, dh)


def pack_transfer_chunk(cache: dict, hit_pages: int, start_page: int,
                        end_page: int | None = None, *, final: bool = True,
                        page_tokens: int = B_TOK):
    """Pack one *streamed chunk* of the cache: attention pages in
    ``[max(hit_pages, start_page), min(end_page, valid))``.

    This is the executable twin of the simulator's ``kv_streaming`` path
    (ChunkPlane): as each prefill chunk's KV becomes ready, its pages are
    packed and shipped while later chunks are still computing.  Sequence-
    length-independent state (Mamba SSM / RWKV WKV / token-shift) is only
    consistent once the whole prompt is processed, so it rides with the
    ``final`` chunk.  Concatenating the chunk tables of a full sweep
    reproduces ``pack_transfer``'s pages and byte total exactly
    (byte conservation, ``tests/test_serving_e2e.py``).

    Returns (buffers dict, total_bytes).
    """
    buffers = {}
    total = 0
    for name, leaf in cache.items():
        if name == "pos" or not hasattr(leaf, "shape"):
            continue
        if name.startswith(("k", "v")) and leaf.ndim == 5:
            pos = int(cache["pos"])
            n_pages_valid = max((pos + page_tokens - 1) // page_tokens, 0)
            lo = max(hit_pages, start_page)
            hi = n_pages_valid if end_page is None else min(end_page, n_pages_valid)
            pool = paged_view(leaf, page_tokens)
            periods = leaf.shape[0]
            pages_per_period = leaf.shape[2] // page_tokens
            table = []
            for per in range(periods):
                for pg in range(lo, hi):
                    table.append(per * pages_per_period + pg)
            if not table:
                continue
            buf = ops.kv_pack(pool, jnp.asarray(table, jnp.int32))
            buffers[name] = (buf, tuple(table))
            total += buf.size * buf.dtype.itemsize
        elif final:
            # Fixed-size state (Mamba/RWKV/pos-independent): ships whole,
            # with the last chunk.
            buffers[name] = (leaf, None)
            total += leaf.size * leaf.dtype.itemsize
    return buffers, total


def pack_transfer(cache: dict, hit_pages: int, page_tokens: int = B_TOK):
    """Pack every non-hit page of the attention KV leaves into one buffer.

    Returns (buffers dict, total_bytes) — the effective transfer payload
    s_eff of Eq. (2), materialised.  Equivalent to a single whole-range
    chunk of :func:`pack_transfer_chunk`.
    """
    return pack_transfer_chunk(cache, hit_pages, 0, None, final=True,
                               page_tokens=page_tokens)


def merge_chunk_buffers(chunks: list[dict]) -> dict:
    """Merge per-chunk buffer dicts (in chunk order) into one transfer-
    equivalent dict suitable for :func:`unpack_transfer`: paged leaves get
    their buffers concatenated along the page axis and their tables
    chained; fixed-state leaves take the last (final-chunk) value."""
    out: dict = {}
    for buffers in chunks:
        for name, (buf, table) in buffers.items():
            if table is None:
                out[name] = (buf, None)
            elif name in out:
                prev, ptab = out[name]
                out[name] = (jnp.concatenate([prev, buf], axis=0),
                             ptab + tuple(table))
            else:
                out[name] = (buf, tuple(table))
    return out


def unpack_transfer(buffers: dict, like_cache: dict, page_tokens: int = B_TOK,
                    *, device=None):
    """Reassemble a per-request cache dict from transfer buffers on
    ``device`` (JAX's default device when None), where the caller has
    already put the buffers."""
    out = {}
    for name, leaf in like_cache.items():
        if name == "pos" or not hasattr(leaf, "shape"):
            continue
        if name in buffers:
            buf, table = buffers[name]
            if table is None:
                out[name] = buf
            else:
                pool = jnp.zeros(
                    (int(np.prod((leaf.shape[0], leaf.shape[2] // page_tokens))),
                     page_tokens, leaf.shape[3], leaf.shape[4]),
                    leaf.dtype, device=device,
                )
                pool = ops.kv_unpack(pool, buf, jnp.asarray(table, jnp.int32))
                out[name] = pool.reshape(leaf.shape)
        else:
            out[name] = jnp.zeros(leaf.shape, leaf.dtype, device=device)
    return out
