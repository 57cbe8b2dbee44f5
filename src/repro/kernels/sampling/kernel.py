"""Pallas TPU kernel: per-row top-k / top-p logit mask without a sort.

A vocab sort is the classic way to find the top-k boundary and the nucleus
cutoff, but sorting 32-128k lanes per row per decode step is exactly the
memory traffic the fused serving step exists to avoid. Both thresholds are
monotone predicates of a scalar, so the kernel bisects instead:

  top-k:  largest t with count(logits >= t) >= k      (t -> k-th logit)
  top-p:  largest t with mass({prob >= t}) >= top_p   (t -> nucleus cutoff)

Each bisection is ITERS vectorized compare+reduce passes over the row held
in VMEM — no gather, no sort, no extra HBM round trip. The converged
threshold sits within (range / 2^ITERS) *below* the exact boundary, so
boundary ties are kept (same semantics as the sort-based oracle in ref.py);
an entry is misclassified only if it lies within that epsilon strictly
below the true cutoff.

Grid: (T,) — one program per batch row; per-row k and top_p ride in via
scalar prefetch (SMEM), like the block tables in kernels/paged_decode.
The row is viewed lane-dense as [Vp/128, 128]: its block spans the last
two dims whole, which Mosaic accepts at any vocab (a (1, Vp) row slice is
not a legal tile), and a 152k-entry f32 row is ~0.6 MB of VMEM. Every
threshold is carried as a (1, 1) vector and every select has a vector
condition — Mosaic does not lower a select on a scalar condition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
ITERS = 30  # f32 bisection: range/2^30 of slack at the boundary
LANES = 128


def _mask_kernel(k_ref, p_ref, x_ref, o_ref, *, V: int):
    t = pl.program_id(0)
    x = x_ref[0].astype(jnp.float32)  # [R, 128]: the row, lane-dense
    col = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
    valid = col < V
    x = jnp.where(valid, x, NEG_INF)
    k = jnp.full((1, 1), k_ref[t], jnp.int32)
    top_p = jnp.full((1, 1), p_ref[t], jnp.float32)

    xmax = jnp.max(x, keepdims=True)  # (1, 1)
    xmin = jnp.min(jnp.where(valid, x, xmax), keepdims=True)

    def k_body(_, lo_hi):
        lo, hi = lo_hi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum(jnp.where(valid & (x >= mid), 1, 0), keepdims=True)
        ok = cnt >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    k_thr, _ = jax.lax.fori_loop(0, ITERS, k_body, (xmin, xmax + 1.0))
    # top_k <= 0 disables the filter: a threshold no entry falls below
    k_thr = jnp.where(k > 0, k_thr, NEG_INF)

    e = jnp.where(valid, jnp.exp(x - xmax), 0.0)
    probs = e / jnp.sum(e, keepdims=True)

    def p_body(_, lo_hi):
        lo, hi = lo_hi
        mid = 0.5 * (lo + hi)
        mass = jnp.sum(jnp.where(probs >= mid, probs, 0.0), keepdims=True)
        ok = mass >= top_p
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    p_thr, _ = jax.lax.fori_loop(
        0, ITERS, p_body,
        (jnp.zeros((1, 1), jnp.float32), jnp.ones((1, 1), jnp.float32)))
    # top_p >= 1 disables the filter (probs are never negative)
    p_thr = jnp.where(top_p < 1.0, p_thr, -1.0)

    keep = (x >= k_thr) & (probs >= p_thr)
    o_ref[0] = jnp.where(keep, x, NEG_INF).astype(o_ref.dtype)


def topk_topp_mask_kernel(logits, top_k, top_p, *, interpret: bool = False):
    """logits [T,V] (any float dtype); top_k [T] int32; top_p [T] f32.

    Returns [T,V] f32 with dropped entries at NEG_INF. V is padded to the
    lane width internally; padded columns never survive the mask.
    """
    T, V = logits.shape
    Vp = -(-V // LANES) * LANES
    if Vp != V:
        pad = jnp.full((T, Vp - V), NEG_INF, logits.dtype)
        logits = jnp.concatenate([logits, pad], axis=1)
    R = Vp // LANES

    kern = functools.partial(_mask_kernel, V=V)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # per-row k and top_p land in SMEM
        grid=(T,),
        in_specs=[pl.BlockSpec((1, R, LANES), lambda t, k, p: (t, 0, 0))],
        out_specs=pl.BlockSpec((1, R, LANES), lambda t, k, p: (t, 0, 0)),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, R, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
      logits.reshape(T, R, LANES))
    out = out.reshape(T, Vp)
    return out[:, :V]
