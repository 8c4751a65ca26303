"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_decode_ref(q, k_cache, v_cache, pos):
    """q: (B,H,dh); k/v: (B,S,KV,dh); pos: scalar -> (B,H,dh)."""
    b, h, dh = q.shape
    _, s, kv, _ = k_cache.shape
    g = h // kv
    qg = q.reshape(b, kv, g, dh).astype(jnp.float32)
    kx = k_cache.astype(jnp.float32)
    vx = v_cache.astype(jnp.float32)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, kx) * dh ** -0.5
    mask = jnp.arange(s)[None, None, None, :] < pos
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vx)
    return out.reshape(b, h, dh).astype(q.dtype)


def kv_pack_ref(pool, block_table):
    return jnp.take(pool, jnp.asarray(block_table, jnp.int32), axis=0)


def kv_unpack_ref(pool, buf, block_table):
    return pool.at[jnp.asarray(block_table, jnp.int32)].set(buf)


def netkv_score_ref(free_mem, queued, batch, hit_tokens, tier, healthy, iter_scale,
                    tier_bw, tier_lat, congestion, n_inflight,
                    *, s_r, input_len, iter_a, iter_b, m_min, beta_max,
                    state_bytes=0.0):
    """Identical arithmetic to repro.core.netkv_jax.score_pool."""
    free_mem = jnp.asarray(free_mem, jnp.float32)
    hit = jnp.minimum(jnp.asarray(hit_tokens, jnp.float32), input_len)
    s_eff = (s_r - state_bytes) * (1.0 - hit / max(input_len, 1.0)) + state_bytes
    tier = jnp.asarray(tier, jnp.int32)
    bw = jnp.asarray(tier_bw, jnp.float32)[tier]
    lat = jnp.asarray(tier_lat, jnp.float32)[tier]
    cong = jnp.asarray(congestion, jnp.float32)[tier]
    infl = jnp.asarray(n_inflight, jnp.float32)[tier]
    beff = bw * (1.0 - cong) / (1.0 + infl)
    t_xfer = s_eff / jnp.maximum(beff, 1e-9) + lat
    batch = jnp.asarray(batch, jnp.float32)
    scale = jnp.asarray(iter_scale, jnp.float32)
    t_iter = (iter_a + iter_b * batch) * scale
    blocked = jnp.maximum(0.0, jnp.asarray(queued, jnp.float32) - (beta_max - batch))
    t_queue = blocked * t_iter
    t_dec = (iter_a + iter_b * (batch + 1.0)) * scale
    cost = t_xfer + t_queue + t_dec
    feasible = (jnp.asarray(healthy, jnp.float32) > 0.5) & (free_mem >= s_eff + m_min)
    cost = jnp.where(feasible, cost, 3.0e38)
    return cost, jnp.argmin(cost).astype(jnp.int32)


def rwkv_scan_ref(r, k, v, w, u):
    """Sequential WKV-6 reference.  r/k/v/w: (B,T,H,dh); u: (H,dh)."""
    b, t, h, dh = r.shape
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(state, xs):
        r_t, k_t, v_t, w_t = xs                          # (B,H,dh)
        kv = k_t[..., :, None] * v_t[..., None, :]       # (B,H,dh,dh)
        y = jnp.sum(r_t[..., :, None] * (state + uf[None, :, :, None] * kv), axis=-2)
        state = state * w_t[..., :, None] + kv
        return state, y

    xs = tuple(x.transpose(1, 0, 2, 3) for x in (rf, kf, vf, wf))
    s0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    final, ys = jax.lax.scan(step, s0, xs)
    return ys.transpose(1, 0, 2, 3).astype(r.dtype), final
