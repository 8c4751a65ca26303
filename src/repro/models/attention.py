"""GQA attention: chunked-causal (prefill/train), single-token (decode),
bidirectional (encoder) and cross (enc-dec decoder) variants.

The prefill/train path never materialises the full S x S score matrix: a
``lax.scan`` over query chunks keeps live memory at (B, Hq, chunk, S) — the
pure-XLA analogue of flash attention, required for the 32K-prefill shapes on
a 16 GB HBM budget.  Decode attention is this XLA path on every backend; no
model calls the Pallas ``flash_decode`` kernel (repro.kernels.ops).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -2.0e38


def _gqa_expand(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, KV, dh) -> (B, S, H, dh) by repeating each KV head H/KV times."""
    b, s, kv, dh = k.shape
    rep = n_heads // kv if n_heads % kv == 0 else -1
    if rep == -1:
        # Non-divisible head ratio (padded sharding archs): tile + slice.
        reps = -(-n_heads // kv)
        return jnp.tile(k[:, :, :, None, :], (1, 1, 1, reps, 1)).reshape(b, s, kv * reps, dh)[
            :, :, :n_heads
        ]
    return jnp.repeat(k, rep, axis=2)


def chunked_causal_attention(
    q: jax.Array,  # (B, S, H, dh)
    k: jax.Array,  # (B, S, KV, dh)
    v: jax.Array,  # (B, S, KV, dh)
    *,
    chunk: int = 1024,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Memory-bounded attention; returns (B, S, H, dh)."""
    b, s, h, dh = q.shape
    kv = k.shape[2]
    scale = scale if scale is not None else dh ** -0.5
    grouped = h % kv == 0
    if grouped:
        g = h // kv
        if s <= chunk:
            return _attn_block_grouped(q.reshape(b, s, kv, g, dh), k, v, 0,
                                       causal, scale)
    else:
        kx = _gqa_expand(k, h)
        vx = _gqa_expand(v, h)
        if s <= chunk:
            return _attn_block(q, kx, vx, 0, causal, scale)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qc = q.reshape(b, n_chunks, chunk, h, dh).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        qi, i = xs
        if grouped:
            out = _attn_block_grouped(qi.reshape(b, chunk, kv, h // kv, dh),
                                      k, v, i * chunk, causal, scale)
        else:
            out = _attn_block(qi, kx, vx, i * chunk, causal, scale)
        return carry, out

    _, outs = jax.lax.scan(body, None, (qc, jnp.arange(n_chunks)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * chunk, h, dh)
    return out[:, :s]


def _attn_block(q, kx, vx, q_offset, causal, scale):
    """q: (B, C, H, dh) against full kx/vx: (B, S, H, dh)."""
    b, c, h, dh = q.shape
    s = kx.shape[1]
    logits = jnp.einsum("bchd,bshd->bhcs", q, kx).astype(jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(c)[:, None]
        k_pos = jnp.arange(s)[None, :]
        logits = jnp.where(k_pos <= q_pos, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhcs,bshd->bchd", probs, vx)


def _attn_block_grouped(qg, k, v, q_offset, causal, scale):
    """Grouped GQA block: qg (B, C, KV, G, dh) against raw k/v (B, S, KV, dh)
    — never materialises the head-expanded (B, S, H, dh) cache (5x the KV
    bytes at 5:1 GQA; the prefill-path analogue of §Perf decode iter 2)."""
    b, c, kv, g, dh = qg.shape
    s = k.shape[1]
    logits = jnp.einsum("bckgd,bskd->bkgcs", qg, k).astype(jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(c)[:, None]
        k_pos = jnp.arange(s)[None, :]
        logits = jnp.where((k_pos <= q_pos)[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(qg.dtype)
    out = jnp.einsum("bkgcs,bskd->bckgd", probs, v)
    return out.reshape(b, c, kv * g, dh)


def decode_attention(
    q: jax.Array,        # (B, 1, H, dh)
    k_cache: jax.Array,  # (B, S_max, KV, dh)
    v_cache: jax.Array,  # (B, S_max, KV, dh)
    pos: jax.Array,      # scalar int: number of valid cache entries
    *,
    k_new: jax.Array | None = None,  # (B, 1, KV, dh): the current token's KV
    v_new: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """One-token attention against a (possibly padded) KV cache.

    With ``k_new``/``v_new`` the cache is treated as READ-ONLY and the
    current token's self-attention term is merged into the softmax — the
    paged-decode formulation that avoids a dynamic-update-slice on a
    sharded cache (a full cache re-gather under GSPMD; see EXPERIMENTS.md
    §Perf iteration on decode_32k).
    """
    b, _, h, dh = q.shape
    s = k_cache.shape[1]
    kv = k_cache.shape[2]
    scale = scale if scale is not None else dh ** -0.5
    if h % kv == 0:
        # Grouped GQA: contract q groups directly against the KV cache —
        # never materialises the (B, S, H, dh) head-expanded cache (5x the
        # cache bytes on 5:1 GQA, and the trigger for GSPMD's seq->heads
        # re-gather; EXPERIMENTS.md §Perf decode iteration 2).
        g = h // kv
        qg = q.reshape(b, 1, kv, g, dh)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache).astype(jnp.float32) * scale
        valid = jnp.arange(s)[None, None, None, None, :] < jnp.asarray(pos).reshape(-1, 1, 1, 1, 1)
        logits = jnp.where(valid, logits, NEG_INF)
        if k_new is not None:
            self_logit = jnp.einsum("bqkgd,bnkd->bkgqn", qg, k_new).astype(jnp.float32) * scale
            logits = jnp.concatenate([logits, self_logit], axis=-1)
            probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
            out = jnp.einsum("bkgqs,bskd->bqkgd", probs[..., :s], v_cache)
            out = out + jnp.einsum("bkgqn,bnkd->bqkgd", probs[..., s:], v_new)
        else:
            probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
            out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_cache)
        return out.reshape(b, 1, h, dh)
    kx = _gqa_expand(k_cache, h)
    vx = _gqa_expand(v_cache, h)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kx).astype(jnp.float32) * scale
    valid = jnp.arange(s)[None, None, None, :] < jnp.asarray(pos).reshape(-1, 1, 1, 1)
    logits = jnp.where(valid, logits, NEG_INF)
    if k_new is not None:
        kn = _gqa_expand(k_new, h)
        vn = _gqa_expand(v_new, h)
        self_logit = jnp.einsum("bqhd,bnhd->bhqn", q, kn).astype(jnp.float32) * scale
        logits = jnp.concatenate([logits, self_logit], axis=-1)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqs,bshd->bqhd", probs[..., :s], vx)
        out = out + jnp.einsum("bhqn,bnhd->bqhd", probs[..., s:], vn)
        return out
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, vx)


def sharded_decode_attention(
    q, k_cache, v_cache, pos, *, mesh, seq_axis: str, scale: float | None = None
):
    """Sequence-parallel decode: the KV cache is sharded along S across
    ``seq_axis``; each shard computes partial (max, num, den) statistics and
    merges with psum — the TPU-native long-context decode path (DESIGN §3).

    Call under shard_map with k_cache/v_cache sharded on dim 1.
    """
    b, _, h, dh = q.shape
    s_local = k_cache.shape[1]
    scale = scale if scale is not None else dh ** -0.5
    idx = jax.lax.axis_index(seq_axis)
    start = idx * s_local
    kx = _gqa_expand(k_cache, h)
    vx = _gqa_expand(v_cache, h)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kx).astype(jnp.float32) * scale
    valid = (start + jnp.arange(s_local))[None, None, None, :] < pos
    logits = jnp.where(valid, logits, NEG_INF)
    local_max = jnp.max(logits, axis=-1, keepdims=True)
    global_max = jax.lax.pmax(local_max, seq_axis)
    p = jnp.exp(logits - global_max)
    num = jnp.einsum("bhqs,bshd->bqhd", p.astype(q.dtype), vx).astype(jnp.float32)
    den = jnp.sum(p, axis=-1)[..., None].transpose(0, 2, 1, 3)  # (B,1,H,1)
    num = jax.lax.psum(num, seq_axis)
    den = jax.lax.psum(den, seq_axis)
    return (num / jnp.maximum(den, 1e-30)).astype(q.dtype)


def cross_attention(
    q: jax.Array,       # (B, S_dec, H, dh)
    k_mem: jax.Array,   # (B, S_enc, KV, dh)
    v_mem: jax.Array,
    *,
    scale: float | None = None,
) -> jax.Array:
    """Full (non-causal) attention over a fixed encoder memory."""
    h = q.shape[2]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    kx = _gqa_expand(k_mem, h)
    vx = _gqa_expand(v_mem, h)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kx).astype(jnp.float32) * scale
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, vx)


def seq_sharded_decode_attention(q, k_cache, v_cache, pos, k_new, v_new, *,
                                 mesh, batch_axes, seq_axes,
                                 scale: float | None = None):
    """Read-only GQA decode attention with the KV cache sharded along S.

    Explicit shard_map: each seq shard computes partial (max, num, den)
    online-softmax statistics and merges with pmax/psum over ``seq_axes`` —
    collectives are O(B*H*dh) per layer instead of GSPMD's full-cache
    re-gather (EXPERIMENTS.md §Perf decode iteration 3).  The self-token
    term is added on shard 0 only.
    """
    b, _, h, dh = q.shape
    kv = k_cache.shape[2]
    assert h % kv == 0
    g = h // kv
    scale = scale if scale is not None else dh ** -0.5
    bt = batch_axes if len(batch_axes) != 1 else batch_axes[0]
    sq = seq_axes if len(seq_axes) != 1 else seq_axes[0]
    seq_axis_names = tuple(seq_axes)

    def local(qg, kc, vc, pos_s, kn, vn):
        s_loc = kc.shape[1]
        idx = jax.lax.axis_index(seq_axis_names)
        start = idx * s_loc
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc).astype(jnp.float32) * scale
        ids = start + jnp.arange(s_loc)
        valid = ids[None, None, None, None, :] < jnp.asarray(pos_s).reshape(-1, 1, 1, 1, 1)
        logits = jnp.where(valid, logits, NEG_INF)
        self_logit = jnp.einsum("bqkgd,bnkd->bkgqn", qg, kn).astype(jnp.float32) * scale
        on_first = (idx == 0)
        self_logit = jnp.where(on_first, self_logit, NEG_INF)
        m_loc = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True),
                            jnp.max(self_logit, axis=-1, keepdims=True))
        m = jax.lax.pmax(m_loc, seq_axis_names)
        pl = jnp.exp(logits - m)
        psl = jnp.exp(self_logit - m)
        num = jnp.einsum("bkgqs,bskd->bkgqd", pl.astype(vc.dtype), vc).astype(jnp.float32)
        num = num + jnp.einsum("bkgqn,bnkd->bkgqd", psl.astype(vn.dtype), vn).astype(jnp.float32)
        den = jnp.sum(pl, axis=-1, keepdims=True) + jnp.sum(psl, axis=-1, keepdims=True)
        num = jax.lax.psum(num, seq_axis_names)
        den = jax.lax.psum(den, seq_axis_names)
        return (num / jnp.maximum(den, 1e-30)).astype(qg.dtype)

    qg = q.reshape(b, 1, kv, g, dh)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bt, None, None, None, None), P(bt, sq, None, None),
                  P(bt, sq, None, None), P(), P(bt, None, None, None),
                  P(bt, None, None, None)),
        out_specs=P(bt, None, None, None, None),
        check_vma=False,
    )(qg, k_cache, v_cache, jnp.asarray(pos, jnp.int32), k_new, v_new)
    return out.reshape(b, 1, h, dh)
