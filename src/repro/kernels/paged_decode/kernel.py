"""Paged flash-decode Pallas TPU kernel.

One-token attention where K/V live in a global block pool [NB, Hkv, bs, hd]
indexed by per-row block tables (serve/blocks.py). The table and the
per-row valid lengths ride in via scalar prefetch (SMEM); the pools stay in
HBM (memory_space=pltpu.HBM) and the kernel copies the blocks it needs
itself, so its work follows each row's live blocks, not the table's width.

Grid: (B,) — one program per row attends with every KV head, each with
its whole GQA group of g = Hq/Hkv query heads: q arrives as [B, Hkv, g, hd]
(a Mosaic-legal block at any g), and a block's [Hkv, bs, hd] is contiguous
in the pool, so one copy brings it for all heads and each K/V block is
read once per group instead of once per query head.

Chunk loop: inside a program a fori_loop runs over the row's live blocks
in chunks of C blocks, C = chunk_blocks(bs, MB): the fewest blocks that
cover 128 key positions (8 at bs 16), clipped to the table width, so a
chunk's scores are one (g, hd) x (hd, C*bs) matmul per KV head. A row at
depth L has live_blocks = min(L // bs + 1, MB) blocks and runs
ceil(live / C) chunks; a masked row (L = -1) runs none and writes zeros.
The online softmax (m, l, acc per query head, f32) rides in the loop
carry.

DMA schedule: for each chunk the kernel starts one async copy per live
block (its id read from the SMEM table) into slot c % 2 of a
double-buffered VMEM scratch [2, C, Hkv, bs, hd] for K and for V, all
signalling that slot's DMA semaphore; chunk c + 1's copies start before
chunk c's are waited on, so the next fetch overlaps this chunk's math.
Blocks past the row's length are never copied: their slots in the last
chunk hold stale or uninitialised data, so their scores are masked to
-inf and their V rows (and, int8, their scales) are zeroed before the
math (0 * NaN is NaN).
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
CHUNK_KEYS = 128  # key positions one chunk's score matmul spans (MXU width)


def block_computes(j, cur_len, bs: int):
    """The kernel's liveness test: block j of a row whose last valid
    position is cur_len is read (it starts at or before it)."""
    return j * bs <= cur_len


def chunk_blocks(bs: int, width: int) -> int:
    """C: blocks per chunk, the fewest that cover CHUNK_KEYS positions,
    clipped to the table width."""
    return min(-(-CHUNK_KEYS // bs), width)


def live_blocks(cur_len, bs: int, width: int):
    """Blocks j < width for which block_computes(j, cur_len, bs) holds
    (numpy or traced): cur_len // bs + 1, none for a masked row (-1)."""
    return (cur_len // bs + 1).clip(0, width)


def grid_programs(rows: int, lengths, bs: int,
                  width: int) -> Tuple[int, int]:
    """Per KV head, the block slots the chunk loop of `_paged_call` visits
    over `rows` rows with tables `width` blocks wide, and how many of them
    hold a live block of the real rows at depths `lengths`: each row runs
    ceil(live / C) chunks of C slots (each slot a block of every KV head).
    The rows past len(lengths) are padding at depth 0: one chunk each,
    never live."""
    C = chunk_blocks(bs, width)
    live = live_blocks(np.asarray(lengths), bs, width)
    chunks = -(-live // C)
    pad = rows - len(live)
    return C * (int(chunks.sum()) + pad), int(live.sum())


def _pd_kernel(tbl_ref, len_ref, q_ref, *refs, scale: float, bs: int,
               C: int, quant: bool):
    """One row's program: the chunk loop over its live blocks, every KV
    head.

    quant: K/V blocks arrive as int8 with their per-token scales (a lane
    row per block and head, copied beside it), and the dequant folds into
    the attention math: q.(k_i*ks_i) = ks_i*(q.k_i) scales the scores and
    sum_i p_i*(v_i*vs_i) = sum_i (p_i*vs_i)*v_i scales the probabilities,
    so no fp copy of a block (or of the pool) exists."""
    n = 4 if quant else 2
    pools, (o_ref,), bufs, (sem,) = (refs[:n], refs[n:n + 1],
                                     refs[n + 1:2 * n + 1], refs[2 * n + 1:])
    b = pl.program_id(0)
    MB = tbl_ref.shape[1]
    Hkv, g, hd = q_ref.shape[1:]
    W = C * bs
    cur_len = len_ref[b]
    n_chunks = pl.cdiv(live_blocks(cur_len, bs, MB), C)

    def copies(c, slot, op):
        for i in range(C):
            j = c * C + i

            @pl.when(block_computes(j, cur_len, bs) & (j < MB))
            def _():
                blk = tbl_ref[b, j]
                for pool, buf in zip(pools, bufs):
                    cp = pltpu.make_async_copy(pool.at[blk], buf.at[slot, i],
                                               sem.at[slot])
                    getattr(cp, op)()

    def keys(buf, slot, h):  # head h of a K/V slot -> [W, hd] f32
        return buf[slot, :, h].astype(jnp.float32).reshape(W, hd)

    def scales(buf, slot, h):
        # block i's bs scales lead its own lane row: roll each to lanes
        # [i*bs, (i+1)*bs) and keep those lanes only -> [1, W]
        rows = buf[slot, :, h]  # [C, 1, lanes]
        lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape[1:], 1)
        row = sum(jnp.where(lane // bs == i, pltpu.roll(rows[i], i * bs, 1),
                            0.0) for i in range(C))
        return row[:, :W]

    def attend(h, slot, live, vlive, carry):  # one head, one chunk
        m_prev, l_prev, acc = carry
        q = q_ref[0, h].astype(jnp.float32)  # [g, hd]
        k = keys(bufs[0], slot, h)
        v = jnp.where(vlive, keys(bufs[1], slot, h), 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            s = s * scales(bufs[2], slot, h)
        s = jnp.where(live, s, NEG_INF)  # [g, W]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quant:
            p = jnp.where(live, p * scales(bufs[3], slot, h), 0.0)
        acc = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    def body(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _prefetch():
            copies(c + 1, 1 - slot, "start")

        copies(c, slot, "wait")
        kpos = c * W + jax.lax.broadcasted_iota(jnp.int32, (g, W), 1)
        vpos = c * W + jax.lax.broadcasted_iota(jnp.int32, (W, hd), 0)
        return tuple(attend(h, slot, kpos <= cur_len, vpos <= cur_len,
                            carry[h]) for h in range(Hkv))

    @pl.when(n_chunks > 0)
    def _first():
        copies(0, 0, "start")

    init = (jnp.full((g, 1), NEG_INF, jnp.float32),
            jnp.zeros((g, 1), jnp.float32), jnp.zeros((g, hd), jnp.float32))
    out = jax.lax.fori_loop(0, n_chunks, body, (init,) * Hkv)
    for h, (_, l, acc) in enumerate(out):
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _lanes(x):
    """x with its last dim zero-padded to whole 128-lane rows: Mosaic
    copies a slice of an HBM ref only when its last dim fills them."""
    pad = -x.shape[-1] % 128
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _paged_call(q, tables, lengths, blocks, *, interpret: bool):
    """pallas_call over grid (B,): q [B,Hq,hd] regrouped to [B,Hkv,g,hd];
    `blocks` (K and V pools, then the int8 kernel's scales) stay in HBM
    and are copied block by block by the chunk loop. A head dim short of
    a multiple of 128 is zero-padded in q and the pools (zeros change no
    score, and the padded output lanes are dropped), which costs a copy
    of the pools per call. Returns o [B,Hq,hd] f32."""
    B, Hq, hd = q.shape
    _, Hkv, bs, _ = blocks[0].shape
    MB = tables.shape[1]
    g = Hq // Hkv
    C = chunk_blocks(bs, MB)
    kern = functools.partial(_pd_kernel, scale=1.0 / math.sqrt(hd), bs=bs,
                             C=C, quant=len(blocks) == 4)
    q, blocks = _lanes(q.reshape(B, Hkv, g, hd)), [_lanes(x) for x in blocks]
    q_spec = pl.BlockSpec((1,) + q.shape[1:], lambda b, tbl, L: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + lengths land in SMEM
        grid=(B,),
        in_specs=[q_spec]
        + [pl.BlockSpec(memory_space=pltpu.HBM) for _ in blocks],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((2, C) + x.shape[1:], x.dtype)
                        for x in blocks]
        + [pltpu.SemaphoreType.DMA((2,))],
    )
    o = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q, *blocks)
    return o[..., :hd].reshape(B, Hq, hd)


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

    The scales are viewed as [NB,Hkv,1,bs] (padded to 128 lanes) so each
    block's scales are one lane row, copied by the chunk loop beside its
    int8 K/V block (SMEM holds only the table and lengths, whatever the
    pool size). Returns o [B,Hq,hd] f32.
    """
    NB, Hkv, bs, _ = k_pool.shape
    ks = jnp.asarray(k_scale, jnp.float32).reshape(NB, Hkv, 1, bs)
    vs = jnp.asarray(v_scale, jnp.float32).reshape(NB, Hkv, 1, bs)
    return _paged_call(q, tables, lengths, [k_pool, v_pool, ks, vs],
                       interpret=interpret)
