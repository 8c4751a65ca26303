"""Jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels run compiled (Mosaic); on CPU they execute in
``interpret=True`` mode, which runs the kernel body op-by-op and is the
validation path of the CPU test suite.  :func:`interpret_mode` is the one
place that decides between the two.  ``force_reference=True`` switches to
the pure-jnp oracle (used by the serving engine when kernels are disabled).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from .flash_decode import flash_decode as _flash_decode
from .kv_pack import kv_pack as _kv_pack, kv_unpack as _kv_unpack
from .netkv_score import netkv_score as _netkv_score
from .rwkv_scan import rwkv_scan as _rwkv_scan


def interpret_mode() -> bool:
    """True when Pallas kernels must run in interpret mode: JAX's default
    backend is not a TPU (the CPU test suite).  Every kernel call site asks
    here; a chip run asserts it is False."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_s", "force_reference"))
def flash_decode(q, k_cache, v_cache, pos, *, block_s: int = 512,
                 force_reference: bool = False):
    if force_reference:
        return _ref.flash_decode_ref(q, k_cache, v_cache, pos)
    return _flash_decode(q, k_cache, v_cache, pos, block_s=block_s,
                         interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("force_reference",))
def kv_pack(pool, block_table, *, force_reference: bool = False):
    if force_reference:
        return _ref.kv_pack_ref(pool, block_table)
    return _kv_pack(pool, block_table, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("force_reference",), donate_argnums=(0,))
def kv_unpack(pool, buf, block_table, *, force_reference: bool = False):
    if force_reference:
        return _ref.kv_unpack_ref(pool, buf, block_table)
    return _kv_unpack(pool, buf, block_table, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=(
    "s_r", "input_len", "iter_a", "iter_b", "m_min", "beta_max", "state_bytes"))
def _netkv_score_ref(free_mem, queued, batch, hit_tokens, tier, healthy, iter_scale,
                     tier_bw, tier_lat, congestion, n_inflight, *,
                     s_r, input_len, iter_a, iter_b, m_min, beta_max,
                     state_bytes):
    return _ref.netkv_score_ref(
        free_mem, queued, batch, hit_tokens, tier, healthy, iter_scale,
        tier_bw, tier_lat, congestion, n_inflight, s_r=s_r,
        input_len=input_len, iter_a=iter_a, iter_b=iter_b, m_min=m_min,
        beta_max=beta_max, state_bytes=state_bytes)


def netkv_score(free_mem, queued, batch, hit_tokens, tier, healthy, iter_scale,
                tier_bw, tier_lat, congestion, n_inflight, *,
                s_r: float, input_len: float, iter_a: float, iter_b: float,
                m_min: float, beta_max: int, state_bytes: float = 0.0,
                force_reference: bool = False):
    """The kernel runs its own program, compiled once per padded shape, so
    request sizes and scalars compile nothing new."""
    kw = dict(s_r=s_r, input_len=input_len, iter_a=iter_a, iter_b=iter_b,
              m_min=m_min, beta_max=beta_max, state_bytes=state_bytes)
    arrs = (free_mem, queued, batch, hit_tokens, tier, healthy, iter_scale,
            tier_bw, tier_lat, congestion, n_inflight)
    if force_reference:
        return _netkv_score_ref(*[jnp.asarray(a) for a in arrs], **kw)
    return _netkv_score(*arrs, interpret=interpret_mode(), **kw)


@functools.partial(jax.jit, static_argnames=("chunk", "force_reference"))
def rwkv_scan(r, k, v, w, u, *, chunk: int = 128, force_reference: bool = False):
    if force_reference:
        return _ref.rwkv_scan_ref(r, k, v, w, u)
    return _rwkv_scan(r, k, v, w, u, chunk=chunk, interpret=interpret_mode())
