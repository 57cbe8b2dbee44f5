"""Public paged-decode ops: Pallas kernel + vectorized XLA gather fallback.

SERVE_PLAN (serve/scheduler.py) picks the Pallas path on TPU; on CPU the
serving hot loop runs layers.attention_paged_decode, which uses
gather_blocks() below to rebuild each row's contiguous KV view and then
the same attention_decode math as the slot pool — that shared fp path is
what keeps the greedy token-exact equivalence tests meaningful without
paying interpret-mode overhead. paged_gather_decode is the standalone
(cfg/env-free) composition of the same gather + masked softmax, used to
cross-check the kernel in tests.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.paged_decode.kernel import (
    paged_flash_decode_kernel,
    paged_flash_decode_quant_kernel,
)


def gather_blocks(pool, tables):
    """[NB,Hkv,bs,hd] pool + [B,MB] tables -> contiguous [B,Hkv,MB*bs,hd]
    per-row KV view (logical order == table order). The one gather
    implementation every XLA paged path shares."""
    B, MB = tables.shape
    _, Hkv, bs, hd = pool.shape
    return pool[tables].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, MB * bs, hd)


def gather_block_scales(scales, tables):
    """[NB,Hkv,bs] scale pool + [B,MB] tables -> [B,Hkv,MB*bs] per-row
    scale view in the same logical order as gather_blocks."""
    B, MB = tables.shape
    _, Hkv, bs = scales.shape
    return scales[tables].transpose(0, 2, 1, 3).reshape(B, Hkv, MB * bs)


def quantize_kv(x, axis=-1, eps=1e-8):
    """Symmetric int8 quantization along `axis` (head_dim): returns
    (int8 values, f32 scales with `axis` reduced). scale = absmax/127,
    floored at eps so all-zero rows round-trip to zeros."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=axis) / 127.0, eps)
    q = jnp.clip(jnp.round(xf / jnp.expand_dims(scale, axis)), -127, 127)
    return q.astype(jnp.int8), scale


@partial(jax.jit, static_argnames=("interpret",))
def paged_flash_decode(q, k_pool, v_pool, tables, lengths, *,
                       interpret: bool = False):
    """Paged decode attention via the Pallas kernel.

    q [B,Hq,hd]; k_pool/v_pool [NB,Hkv,bs,hd]; tables [B,MB]; lengths [B].
    Returns [B,Hq,hd] f32 (callers cast)."""
    return paged_flash_decode_kernel(q, k_pool, v_pool, tables, lengths,
                                     interpret=interpret)


@jax.jit
def paged_gather_decode(q, k_pool, v_pool, tables, lengths):
    """XLA composition: gather_blocks + masked softmax attention — same
    math as the kernel, one materialized copy of the gathered KV."""
    B, Hq, hd = q.shape
    Hkv = k_pool.shape[1]
    g = Hq // Hkv
    kg = gather_blocks(k_pool, tables)
    vg = gather_blocks(v_pool, tables)
    qg = q.reshape(B, Hkv, g, hd)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, kg).astype(jnp.float32)
    s = s / math.sqrt(hd)
    kpos = jnp.arange(kg.shape[2])[None, None, None, :]
    s = jnp.where(kpos <= lengths[:, None, None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    a = jnp.where(jnp.isfinite(a), a, 0.0)
    o = jnp.einsum("bhgk,bhkd->bhgd", a, vg.astype(jnp.float32))
    return o.reshape(B, Hq, hd)


@partial(jax.jit, static_argnames=("interpret",))
def paged_flash_decode_quant(q, k_pool, v_pool, k_scale, v_scale, tables,
                             lengths, *, interpret: bool = False):
    """Quantized paged decode attention via the Pallas kernel.

    q [B,Hq,hd]; k_pool/v_pool [NB,Hkv,bs,hd] int8; k_scale/v_scale
    [NB,Hkv,bs] f32; tables [B,MB]; lengths [B]. Returns [B,Hq,hd] f32."""
    return paged_flash_decode_quant_kernel(q, k_pool, v_pool, k_scale,
                                           v_scale, tables, lengths,
                                           interpret=interpret)


@jax.jit
def paged_gather_decode_quant(q, k_pool, v_pool, k_scale, v_scale, tables,
                              lengths):
    """XLA composition for the quant backend: gather int8 blocks + scales,
    dequantize, then the same masked softmax as paged_gather_decode."""
    B, Hq, hd = q.shape
    Hkv = k_pool.shape[1]
    g = Hq // Hkv
    kg = (gather_blocks(k_pool, tables).astype(jnp.float32)
          * gather_block_scales(k_scale, tables)[..., None])
    vg = (gather_blocks(v_pool, tables).astype(jnp.float32)
          * gather_block_scales(v_scale, tables)[..., None])
    qg = q.reshape(B, Hkv, g, hd)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, kg).astype(jnp.float32)
    s = s / math.sqrt(hd)
    kpos = jnp.arange(kg.shape[2])[None, None, None, :]
    s = jnp.where(kpos <= lengths[:, None, None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    a = jnp.where(jnp.isfinite(a), a, 0.0)
    o = jnp.einsum("bhgk,bhkd->bhgd", a, vg)
    return o.reshape(B, Hq, hd)
