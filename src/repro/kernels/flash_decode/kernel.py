"""Flash-decoding Pallas TPU kernel: one-token attention over a long KV cache.

Used for the sequence-sharded KV cache layout (DESIGN.md §4): each TP shard
runs this kernel over its cache slice producing a partial (o, m, l); the
shard_map wrapper in ops.py merges partials with logsumexp weights across the
TP axis. cur_len arrives via scalar prefetch (SMEM) so masked cache blocks
past the current length are skipped entirely.

Grid: (B, Hq, n_kv_blocks) — kv innermost/sequential; scratch carries (m,l,acc).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
               m_sc, l_sc, acc_sc, *, scale: float, bk: int):
    j = pl.program_id(2)
    cur_len = len_ref[0]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    k_start = j * bk

    @pl.when(k_start <= cur_len)
    def _compute():
        q = q_ref[...].reshape(1, -1).astype(jnp.float32)  # [1, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos <= cur_len, s, NEG_INF)
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    n_kv = pl.num_programs(2)

    @pl.when(j == n_kv - 1)
    def _flush():
        o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(
            o_ref.dtype).reshape(o_ref.shape)
        m_ref[...] = m_sc[...].reshape(m_ref.shape)
        l_ref[...] = l_sc[...].reshape(l_ref.shape)


def flash_decode_kernel(q, k, v, cur_len, *, block_k: int = 512,
                        interpret: bool = False):
    """q: [B,Hq,hd]; k,v: [B,Hkv,S,hd]; cur_len: scalar int32 (local index of
    the last valid cache entry; -1 for an all-masked shard).

    Returns (o [B,Hq,hd], m [B,Hq,1], l [B,Hq,1]) — partial softmax stats for
    the cross-shard merge."""
    B, Hq, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    g = Hq // Hkv
    bk = min(block_k, S)
    assert S % bk == 0
    nk = S // bk
    scale = 1.0 / math.sqrt(hd)

    kern = functools.partial(_fd_kernel, scale=scale, bk=bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, h, j, L: (b, h, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, L, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, L, g=g: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, h, j, L: (b, h, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, j, L: (b, h, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, j, L: (b, h, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    q3 = q.reshape(B, Hq, 1, hd)[:, :, 0]  # ensure contiguous [B,Hq,hd]
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(cur_len, jnp.int32).reshape(1), q3, k, v)


def merge_partials(o, m, l, axis_name: str):
    """LSE-merge partial attention outputs across a sharded cache axis.

    o: [B,Hq,hd] f32 (already normalized per shard), m/l: [B,Hq,1].
    """
    m_g = jax.lax.pmax(m, axis_name)
    w = l * jnp.exp(m - m_g)  # effective weight of each shard
    denom = jax.lax.psum(w, axis_name)
    num = jax.lax.psum(o * w, axis_name)
    return num / jnp.maximum(denom, 1e-30)
