"""Pallas TPU rwkv_scan: chunked WKV-6 recurrence.

RWKV-6 prefill is a sequential recurrence over time; the pure-jnp path
(repro.models.rwkv) scans one token at a time with the (dh x dh) state in
HBM-resident carry.  This kernel processes ``chunk`` tokens per grid step
with the state held in VMEM scratch across the sequential chunk axis, so
the state never round-trips HBM — the TPU-hierarchy adaptation of the
CUDA wkv kernel (which keeps state in registers/shared memory).

Inputs r,k,v,w: (B, T, H, dh); u: (H, dh).  Outputs y: (B, T, H, dh) and the
final state (B, H, dh, dh) for decode handoff / state transfer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref, state_scr,
                 *, chunk: int):
    ci = pl.program_id(2)
    n_c = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    u = u_ref[0].astype(jnp.float32)                     # (dh,)

    def step(t, state):
        r = r_ref[0, t, 0, :].astype(jnp.float32)        # (dh,)
        k = k_ref[0, t, 0, :].astype(jnp.float32)
        v = v_ref[0, t, 0, :].astype(jnp.float32)
        w = w_ref[0, t, 0, :].astype(jnp.float32)
        kv = k[:, None] * v[None, :]                     # (dh, dh)
        y = jnp.sum(r[:, None] * (state + u[:, None] * kv), axis=0)
        y_ref[0, t, 0, :] = y.astype(y_ref.dtype)
        return state * w[:, None] + kv

    state = jax.lax.fori_loop(0, chunk, step, state_scr[...])
    state_scr[...] = state

    @pl.when(ci == n_c - 1)
    def _finish():
        s_out_ref[0, 0] = state_scr[...].astype(s_out_ref.dtype)


def rwkv_scan(r, k, v, w, u, *, chunk: int = 128, interpret: bool = False):
    """Chunked WKV-6.  r/k/v/w: (B, T, H, dh) with w the per-step decay in
    (0,1); u: (H, dh) bonus.  Returns (y, final_state)."""
    b, t, h, dh = r.shape
    assert t % chunk == 0, (t, chunk)
    grid = (b, h, t // chunk)
    kernel = functools.partial(_rwkv_kernel, chunk=chunk)
    in_spec = pl.BlockSpec((1, chunk, 1, dh), lambda bi, hi, ci: (bi, ci, hi, 0))
    y, s_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[in_spec, in_spec, in_spec, in_spec,
                  pl.BlockSpec((1, dh), lambda bi, hi, ci: (hi, 0))],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, dh), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, h, dh), r.dtype),
            jax.ShapeDtypeStruct((b, h, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u)
    return y, s_out
