"""JAX/Pallas progressive water-filling: FlowPlane's fixed point, jitted.

``FlowPlane._recompute_rates`` is the per-event hot loop of the network
model: each round divides residual link capacities by unfixed-flow counts,
argmins for the bottleneck link (first-encounter tie-break), fixes every
unfixed flow crossing it at the bottleneck share, and subtracts that share
from the capacities along their paths.  This module re-expresses the whole
fixed point as a ``lax.while_loop`` over padded fixed-width tables so it can
be jitted, ``vmap``ed over a scenario axis, and fused into the ScenarioPlane
sweep program (``sim/scenarios.py``).

Bit-exactness (``backend="jax"``, f64): the JAX path reproduces the NumPy
plane's rates and per-round bottleneck (link, share) sequence exactly:

* the encounter permutation is rebuilt with ``.at[flat].min`` + stable
  argsort — inactive (masked) rows are routed to the pad link, which never
  participates in the argmin (count 0 -> share inf), and the *relative*
  order of real links is unchanged, so the first-minimum tie-break matches;
* per-round capacity updates subtract the *same* share from each target, so
  XLA's scatter-add order cannot change the result; count updates are exact
  integers;
* shares are single f64 divisions and the argmin picks the first minimum in
  scan order — IEEE-identical to ``np.argmin`` on CPU.

``backend="pallas"`` swaps the inner share/argmin reduction for a TPU
Pallas kernel (f32, ``interpret=True`` off-TPU, following the
``netkv_score`` pattern) and is tolerance-tested, not bit-exact — the NumPy
plane stays the parity oracle either way.

The loop body is a no-op once a problem instance converges (all shares inf
-> zero deltas, rates untouched), which is what makes ``vmap`` over a batch
of instances with different round counts sound: converged lanes idle while
stragglers finish.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.jaxutil import enable_f64
from .ops import interpret_mode

LANES = 128
BIG = 3.0e38
# Block indices typed int32: a bare 0 turns int64 under jax_enable_x64,
# which Mosaic refuses in an index_map.
_I0 = np.int32(0)


# ------------------------------------------------------------ Pallas kernel
def _share_argmin_kernel(caps_ref, counts_ref, best_ref, share_ref, *,
                         n_real: int):
    """shares = caps/counts where counts>0 (else BIG); emit (argmin, min).

    Constants and the argmin index are typed f32/int32 so the kernel
    lowers the same whether or not ``jax_enable_x64`` is on (Mosaic has no
    f64 and takes int32 reduction indices only)."""
    f32 = jnp.float32
    caps = caps_ref[...]
    counts = counts_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, caps.shape, 1)
    ok = (counts > f32(0.0)) & (lane < jnp.int32(n_real))
    shares = jnp.where(ok, caps / jnp.where(ok, counts, f32(1.0)), f32(BIG))
    best_ref[0, 0] = jax.lax.argmin(shares[0], 0, jnp.int32)
    # min == shares[argmin] bitwise; a reduction avoids a dynamic gather.
    share_ref[0, 0] = jnp.min(shares)


def _pallas_share_argmin(caps_p, counts, interpret: bool):
    """One water-filling round's bottleneck pick as a fused VMEM pass."""
    n = caps_p.shape[0]
    dp = -(-n // LANES) * LANES
    pad = dp - n
    c = jnp.asarray(caps_p, jnp.float32)
    k = jnp.asarray(counts, jnp.float32)
    if pad:
        c = jnp.pad(c, (0, pad))
        k = jnp.pad(k, (0, pad))
    kern = functools.partial(_share_argmin_kernel, n_real=n)
    best, share = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[pl.BlockSpec((1, dp), lambda i: (_I0, _I0))] * 2,
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (_I0, _I0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i: (_I0, _I0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(c.reshape(1, dp), k.reshape(1, dp))
    return best[0, 0], share[0, 0]


# ------------------------------------------------------------- fixed point
def waterfill_fixed_point(paths, caps, active, *, use_pallas: bool = False,
                          interpret: bool = False):
    """Traceable max-min fair fixed point (jit/vmap-safe, fixed shapes).

    Args:
      paths: (F, H) int32 link ids per flow; short paths padded with the
        virtual pad link ``L`` (``caps.shape[0] - 1``).
      caps: (L + 1,) residual link capacities, ``caps[L] = +inf``.
      active: (F,) bool; inactive rows get rate 0 and touch nothing.

    Returns ``(rates (F,), trace_links (F,), trace_shares (F,), n_rounds)``
    where the trace records each round's bottleneck in *original* link ids
    (−1 padding past ``n_rounds``) — the sequence
    ``FlowPlane._recompute_rates`` logs into ``_wf_trace``.
    """
    F, H = paths.shape
    lp1 = caps.shape[0]
    pad_link = lp1 - 1
    dtype = jnp.float32 if use_pallas else caps.dtype
    caps = caps.astype(dtype)
    active = active.astype(bool)
    P0 = jnp.where(active[:, None], paths.astype(jnp.int32),
                   jnp.int32(pad_link))
    flat = P0.ravel()
    npos = flat.shape[0]
    # First-encounter order (flow-creation x hop): the reference tie-break.
    enc = jnp.full(lp1, npos + 1, jnp.int64)
    enc = enc.at[flat].min(jnp.arange(npos, dtype=jnp.int64))
    perm = jnp.argsort(enc, stable=True)
    inv = jnp.zeros(lp1, jnp.int32).at[perm].set(
        jnp.arange(lp1, dtype=jnp.int32))
    P = inv[P0]
    counts0 = jnp.zeros(lp1, jnp.int64).at[P.ravel()].add(1)
    ppad = inv[pad_link]
    counts0 = counts0.at[ppad].set(0)
    caps_p0 = caps[perm]
    tr_n = max(F, 1)
    state = (
        jnp.zeros(F, dtype),                       # rates
        active,                                    # unfixed
        caps_p0,
        counts0,
        jnp.full(tr_n, -1, jnp.int32),             # trace: bottleneck links
        jnp.full(tr_n, jnp.inf, dtype),            # trace: bottleneck shares
        jnp.int32(0),                              # rounds completed
        active.sum(dtype=jnp.int32),               # flows still unfixed
    )

    def cond(st):
        return st[7] > 0

    def body(st):
        rates, unfixed, caps_p, counts, tl, ts, r, nuf = st
        if use_pallas:
            lid, share = _pallas_share_argmin(caps_p, counts, interpret)
            share = share.astype(dtype)
            is_inf = share >= jnp.array(BIG * 0.5, dtype)
        else:
            shares = jnp.where(counts > 0, caps_p / counts.astype(dtype),
                               jnp.array(jnp.inf, dtype))
            lid = jnp.argmin(shares).astype(jnp.int32)
            share = shares[lid]
            is_inf = jnp.isinf(share)
        onb = unfixed & (P == lid).any(axis=1)
        newly = jnp.where(is_inf, unfixed, onb)    # inf: reference breaks,
        rates = jnp.where(                         # stranding rest at inf
            newly, jnp.where(is_inf, jnp.array(jnp.inf, dtype), share), rates)
        # Fixed rows subtract along their whole padded path (pad hops land
        # on ppad, capacity +inf — mirroring the reference); non-fixed rows
        # are routed to ppad with the same share, a pure no-op.
        sub = jnp.where(is_inf, jnp.array(0, dtype), share)
        idx = jnp.where((onb & ~is_inf)[:, None], P, ppad).ravel()
        caps_p = jnp.maximum(caps_p.at[idx].add(-sub), 0.0)
        counts = counts.at[idx].add(-1)
        nfixed = newly.sum(dtype=jnp.int32)
        nuf = jnp.where(is_inf, jnp.int32(0), nuf - nfixed)
        unfixed = unfixed & ~newly
        tl = tl.at[r].set(jnp.where(is_inf, tl[r],
                                    perm[lid].astype(jnp.int32)))
        ts = ts.at[r].set(jnp.where(is_inf, ts[r], share))
        r = r + jnp.where(is_inf, jnp.int32(0), jnp.int32(1))
        return (rates, unfixed, caps_p, counts, tl, ts, r, nuf)

    rates, _, _, _, tl, ts, r, _ = jax.lax.while_loop(cond, body, state)
    return rates, tl, ts, r


# ------------------------------------------------- parallel fixed point
def _shares_kernel(caps_ref, counts_ref, out_ref):
    """Elementwise fair shares: caps/counts where counts>0, BIG elsewhere."""
    f32 = jnp.float32
    caps = caps_ref[...]
    counts = counts_ref[...]
    ok = counts > f32(0.0)
    out_ref[...] = jnp.where(ok, caps / jnp.where(ok, counts, f32(1.0)),
                             f32(BIG))


def _pallas_shares(caps, counts, interpret: bool):
    n = caps.shape[0]
    dp = -(-n // LANES) * LANES
    pad = dp - n
    c = jnp.asarray(caps, jnp.float32)
    k = jnp.asarray(counts, jnp.float32)
    if pad:
        c = jnp.pad(c, (0, pad))
        k = jnp.pad(k, (0, pad))
    out = pl.pallas_call(
        _shares_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((1, dp), lambda i: (_I0, _I0))] * 2,
        out_specs=pl.BlockSpec((1, dp), lambda i: (_I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
    )(c.reshape(1, dp), k.reshape(1, dp))
    return out[0, :n]


def waterfill_rates_fast(paths, caps, active, *, nhops=None,
                         use_pallas: bool = False, interpret: bool = False):
    """Parallel-bottleneck max-min fixed point: same allocation, ~levels
    rounds instead of ~flows rounds, scatter-free dense rounds.

    The progressive solver (:func:`waterfill_fixed_point`) fixes **one**
    bottleneck link per round to reproduce the reference's per-round trace
    — so F link-disjoint transfers cost F rounds even though they are
    independent.  This variant applies the classic parallel water-filling
    step instead: every link whose fair share is minimal along *all* of
    its unfixed flows' paths is a level bottleneck, and all of them fix
    simultaneously.  The max-min allocation is unique, so the rates agree
    with the progressive solver (up to residual-subtraction rounding —
    tolerance-tested, not bitwise); the per-round trace is not defined
    here.

    Everything runs on the dense flow->link incidence table ``nhops``
    (F, L + 1): each round's unfixed-flow counts and consumed capacities
    are matvecs and the per-flow/per-link minima are masked reduces — no
    scatters, whose element-serial CPU lowering under ``vmap`` dominated
    the ScenarioPlane sweep's step cost.  Callers with static routing can
    pass ``nhops`` precomputed (hops of flow f on link l; the pad column
    is re-zeroed and inactive rows masked here), skipping the one-hot
    build — the ScenarioPlane gathers per-(prefill, decode) incidence
    rows instead of rebuilding them every dt step.
    """
    lp1 = caps.shape[0]
    pad_link = lp1 - 1
    dtype = jnp.float32 if use_pallas else caps.dtype
    caps0 = caps.astype(dtype)
    active = active.astype(bool)
    inf = jnp.array(jnp.inf, dtype)
    if nhops is None:
        P = jnp.where(active[:, None], paths.astype(jnp.int32),
                      jnp.int32(pad_link))
        nhops = (P[:, :, None]
                 == jnp.arange(lp1, dtype=jnp.int32)[None, None, :]
                 ).sum(axis=1).astype(dtype)
    else:
        nhops = jnp.where(active[:, None], nhops.astype(dtype), 0)
    nhops = nhops.at[:, pad_link].set(0)
    F = nhops.shape[0]
    on_f = nhops > 0.5                             # (F, lp1) once per call
    state = (
        jnp.zeros(F, dtype),                       # rates (fixed flows)
        active,                                    # unfixed
        active.sum(dtype=jnp.int32),               # flows still unfixed
    )

    def cond(st):
        return st[2] > 0

    def body(st):
        rates, unfixed, nuf = st
        counts = unfixed.astype(dtype) @ nhops               # (lp1,)
        used = jnp.where(jnp.isfinite(rates), rates,
                         jnp.array(0, dtype)) @ nhops
        caps_c = jnp.maximum(caps0 - used, 0.0)
        if use_pallas:
            shares = _pallas_shares(caps_c, counts, interpret).astype(dtype)
            shares = jnp.where(shares >= jnp.array(BIG * 0.5, dtype), inf,
                               shares)
        else:
            shares = jnp.where(counts > 0.5, caps_c / counts, inf)
        live = on_f & unfixed[:, None]
        sfmat = jnp.where(live, shares[None, :], inf)        # (F, lp1)
        s_f = sfmat.min(axis=1)                    # per-flow bottleneck share
        # Per-link min of its unfixed flows' bottleneck shares: link l is a
        # level bottleneck iff share_l <= that min, i.e. every flow on l
        # has its path minimum at l.
        lfm = jnp.where(live, s_f[:, None], inf).min(axis=0)
        fixable = (counts > 0.5) & (shares <= lfm)
        fix = unfixed & jnp.isfinite(s_f) & (
            live & fixable[None, :] & (shares[None, :] <= s_f[:, None])
        ).any(axis=1)
        anyfix = fix.any()
        # Stall (no finite share left): strand the rest at inf, mirroring
        # the progressive solver's break.
        rates = jnp.where(fix, s_f, rates)
        rates = jnp.where(~anyfix & unfixed, inf, rates)
        nuf = jnp.where(anyfix, nuf - fix.sum(dtype=jnp.int32),
                        jnp.int32(0))
        unfixed = jnp.where(anyfix, unfixed & ~fix,
                            jnp.zeros_like(unfixed))
        return (rates, unfixed, nuf)

    rates, _, _ = jax.lax.while_loop(cond, body, state)
    return rates


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _waterfill_jit(paths, caps, active, *, use_pallas, interpret):
    return waterfill_fixed_point(paths, caps, active, use_pallas=use_pallas,
                                 interpret=interpret)


def waterfill_rates(paths, caps, active=None, *, backend: str = "jax"):
    """Public entry: jitted water-filling over one flow table.

    ``backend="jax"`` is the f64 bit-exact path; ``backend="pallas"`` runs
    the inner reduction as a TPU kernel (f32, interpret mode off-TPU).
    """
    enable_f64()
    if backend not in ("jax", "pallas"):
        raise ValueError(f"unknown waterfill backend {backend!r}")
    paths = jnp.asarray(paths, jnp.int32)
    caps = jnp.asarray(caps, jnp.float64)
    if active is None:
        active = jnp.ones(paths.shape[0], bool)
    else:
        active = jnp.asarray(active, bool)
    return _waterfill_jit(paths, caps, active,
                          use_pallas=(backend == "pallas"),
                          interpret=interpret_mode())
