"""Paged flash-decode Pallas TPU kernel.

One-token attention where K/V live in a global block pool [NB, Hkv, bs, hd]
indexed by per-row block tables (serve/blocks.py). Both the table and the
per-row valid lengths ride in via scalar prefetch (SMEM): the table entry
feeds the K/V BlockSpec index maps directly — the gather IS the DMA
schedule, no materialized [B, MB*bs] copy of the cache. Blocks past a
row's current length skip both compute (pl.when) and HBM traffic: their
index map clamps to the row's last valid block, and the Pallas pipeline
elides the copy when consecutive grid steps name the same block — so a
row that has decoded 40 tokens reads ceil(40/bs) blocks no matter how
wide its table is.

Grid: (B, Hkv, MB) — one program per (row, KV head) attends with the whole
GQA group of g = Hq/Hkv query heads: q arrives as [B, Hkv, g, hd], so its
block (g, hd) spans the last two dims whole (a Mosaic-legal tile at any g)
and each K/V block is read once per group instead of once per query head.
Blocks are innermost/sequential; scratch carries the online-softmax
(m, l, acc) per query head like kernels/flash_decode.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def block_computes(j, cur_len, bs: int):
    """The kernel's liveness test: grid step j of a row whose last valid
    position is cur_len computes (its block starts at or before it)."""
    return j * bs <= cur_len


def grid_programs(rows: int, lengths, bs: int,
                  width: int) -> Tuple[int, int]:
    """Per KV head, the programs of the grid `_paged_call` runs over
    `rows` rows with tables `width` blocks wide, and how many of them
    compute for the rows at depths `lengths`: block j computes where
    block_computes(j, length, bs), so min(length // bs + 1, width) of a
    row's blocks, none of a masked row's (-1)."""
    live = np.clip(np.asarray(lengths) // bs + 1, 0, width)
    return rows * width, int(live.sum())


def _pd_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *refs, scale: float,
               bs: int, quant: bool):
    """One (row, KV head, block) step of the online softmax.

    quant: K/V blocks arrive as int8 with their per-token scales ((1, bs)
    f32 VMEM blocks on the same index map), and the dequant folds into the
    attention math: q.(k_i*ks_i) = ks_i*(q.k_i) scales the scores and
    sum_i p_i*(v_i*vs_i) = sum_i (p_i*vs_i)*v_i scales the probabilities,
    so no fp copy of a block (or of the pool) exists."""
    if quant:
        ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc = refs
    else:
        o_ref, m_sc, l_sc, acc_sc = refs
    b = pl.program_id(0)
    j = pl.program_id(2)
    cur_len = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    k_start = j * bs

    @pl.when(block_computes(j, cur_len, bs))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [g, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bs, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            s = s * ks_ref[0, 0]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= cur_len, s, NEG_INF)  # [g, bs]
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quant:
            p = p * vs_ref[0, 0]
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(
            o_ref.dtype)


def _paged_call(q, tables, lengths, blocks, *, interpret: bool):
    """pallas_call over grid (B, Hkv, MB): q [B,Hq,hd] regrouped to
    [B,Hkv,g,hd]; `blocks` (K and V pools, then the int8 kernel's scales)
    follow the clamped table index map. Returns o [B,Hq,hd] f32."""
    B, Hq, hd = q.shape
    _, Hkv, bs, _ = blocks[0].shape
    MB = tables.shape[1]
    g = Hq // Hkv
    kern = functools.partial(_pd_kernel, scale=1.0 / math.sqrt(hd), bs=bs,
                             quant=len(blocks) == 4)

    def kv_index(b, h, j, tbl, L):
        # clamp dead blocks (j past the row's length) to the last live one:
        # revisiting the same block index makes the pipeline skip the copy
        j_live = jnp.maximum(jnp.minimum(j, L[b] // bs), 0)
        return (tbl[b, j_live], h, 0, 0)

    def q_index(b, h, j, tbl, L):
        return (b, h, 0, 0)

    blk_specs = [pl.BlockSpec((1, 1) + x.shape[2:], kv_index)
                 for x in blocks]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + lengths land in SMEM
        grid=(B, Hkv, MB),
        in_specs=[pl.BlockSpec((1, 1, g, hd), q_index)] + blk_specs,
        out_specs=pl.BlockSpec((1, 1, g, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q.reshape(B, Hkv, g, hd), *blocks)
    return o.reshape(B, Hq, hd)


def paged_flash_decode_kernel(q, k_pool, v_pool, tables, lengths, *,
                              interpret: bool = False):
    """q: [B,Hq,hd]; k_pool/v_pool: [NB,Hkv,bs,hd]; tables: [B,MB] int32
    physical block ids; lengths: [B] int32 last valid logical position
    (-1 = row fully masked -> zero output).

    Returns o [B,Hq,hd] f32.
    """
    return _paged_call(q, tables, lengths, [k_pool, v_pool],
                       interpret=interpret)


def paged_flash_decode_quant_kernel(q, k_pool, v_pool, k_scale, v_scale,
                                    tables, lengths, *,
                                    interpret: bool = False):
    """Quantized paged decode. q: [B,Hq,hd]; k_pool/v_pool: [NB,Hkv,bs,hd]
    int8; k_scale/v_scale: [NB,Hkv,bs] f32 per-row dequant scales; tables:
    [B,MB] int32; lengths: [B] int32 (-1 = fully masked).

    The scales are viewed as [NB,Hkv,1,bs] so each block's scales are one
    lane-major (1, bs) VMEM tile fetched by the same table-driven index map
    as its int8 K/V block (SMEM holds only the table and lengths, whatever
    the pool size). Returns o [B,Hq,hd] f32.
    """
    NB, Hkv, bs, _ = k_pool.shape
    ks = jnp.asarray(k_scale, jnp.float32).reshape(NB, Hkv, 1, bs)
    vs = jnp.asarray(v_scale, jnp.float32).reshape(NB, Hkv, 1, bs)
    return _paged_call(q, tables, lengths, [k_pool, v_pool, ks, vs],
                       interpret=interpret)
