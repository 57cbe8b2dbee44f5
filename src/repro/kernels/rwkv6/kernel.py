"""RWKV-6 (Finch) WKV Pallas TPU kernel: chunked state recurrence.

o_t = r_t^T S_{t-1} + (r_t . (u*k_t)) v_t ;  S_t = diag(w_t) S_{t-1} + k_t v_t^T

TPU adaptation: the per-head [hd, hd] state lives in VMEM scratch across the
chunk grid dimension; each chunk closes the intra-chunk interaction with the
bounded pairwise-decay tensor (same stability trick as kernels/rglru) and two
MXU matmuls against the carried state.

Grid: (B, H, n_chunks) — chunks innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sfin_ref, s_sc,
                *, ct: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_sc[...] = jnp.zeros_like(s_sc)

    r = r_ref[0, 0].astype(jnp.float32)  # [ct, hd]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)  # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)  # [1, hd]

    L = jnp.cumsum(lw, axis=0)  # [ct, hd]
    Lprev = L - lw
    # intra-chunk strictly-lower interactions
    diff = Lprev[:, None, :] - L[None, :, :]  # [t, s, hd]
    strict = (jax.lax.broadcasted_iota(jnp.int32, (ct, ct), 0)
              > jax.lax.broadcasted_iota(jnp.int32, (ct, ct), 1))
    D = jnp.where(strict[..., None], jnp.exp(jnp.clip(diff, -60.0, 0.0)), 0.0)
    A = jnp.einsum("tc,sc,tsc->ts", r, k, D)
    Au = jnp.sum(r * (u * k), axis=-1)  # diagonal bonus term [ct]
    o = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o = o + Au[:, None] * v
    # carried-state contribution
    rP = r * jnp.exp(jnp.clip(Lprev, -60.0, 0.0))
    o = o + jax.lax.dot_general(rP, s_sc[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    # state update
    LT = L[-1]  # [hd]
    kT = k * jnp.exp(jnp.clip(LT[None, :] - L, -60.0, 0.0))
    s_sc[...] = (jnp.exp(jnp.clip(LT, -60.0, 0.0))[:, None] * s_sc[...]
                 + jax.lax.dot_general(kT, v, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32))

    @pl.when(c == n_chunks - 1)
    def _flush():
        sfin_ref[0, 0] = s_sc[...]


def wkv6_kernel(r, k, v, logw, u, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,logw: [B,H,S,hd]; u: [H,hd]. Returns (o [B,H,S,hd] f32,
    s_final [B,H,hd,hd] f32)."""
    B, H, S, hd = r.shape
    ct = min(chunk, S)
    assert S % ct == 0
    n = S // ct
    kern = functools.partial(_wkv_kernel, ct=ct, n_chunks=n)
    return pl.pallas_call(
        kern,
        grid=(B, H, n),
        in_specs=[
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hd), lambda b, h, c: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, logw, u)
