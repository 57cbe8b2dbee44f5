"""Flash attention Pallas TPU kernel (causal / sliding-window / GQA).

TPU adaptation of the FlashAttention blocking (DESIGN.md §2): q/k/v tiles are
DMA'd HBM->VMEM per BlockSpec; the online-softmax statistics (m, l) and the
f32 accumulator live in VMEM scratch across the kv grid dimension; the MXU
consumes (bq, hd) x (hd, bk) tiles (hd and block sizes multiples of 128 on
real configs). Causally-masked kv blocks are *skipped* via pl.when — the
XLA fallback path cannot skip them, which is exactly the gap the kernel
closes on hardware.

Grid: (B, Hq, n_q_blocks, n_kv_blocks), kv innermost ("arbitrary" semantics,
sequential) so scratch carries across kv steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *, scale: float,
               causal: bool, window: int, bq: int, bk: int, n_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = iq * bq
    k_start = ik * bk

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok &= qpos >= kpos
        if window > 0:
            ok &= (qpos - kpos) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_sc[...]
        l_prev = l_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_new
        l_sc[...] = l_new

    if causal or window > 0:
        needed = jnp.ones((), jnp.bool_)
        if causal:
            needed &= k_start <= q_start + bq - 1
        if window > 0:
            needed &= (k_start + bk - 1) >= (q_start - window + 1)
        pl.when(needed)(_compute)
    else:
        _compute()

    # flush on the last kv step for this q block
    if causal:
        last = jnp.minimum(n_kv - 1, (q_start + bq - 1) // bk)
    else:
        last = n_kv - 1

    @pl.when(ik == last)
    def _flush():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(
            o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: [B,Hq,Sq,hd]; k,v: [B,Hkv,Sk,hd]. Returns [B,Hq,Sq,hd]."""
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)

    kern = functools.partial(_fa_kernel, scale=scale, causal=causal,
                             window=window, bq=bq, bk=bk, n_kv=nk)
    return pl.pallas_call(
        kern,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
