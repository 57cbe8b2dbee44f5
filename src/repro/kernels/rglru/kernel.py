"""RG-LRU linear-recurrence Pallas TPU kernel (Griffin recurrent block).

h_t = a_t * h_{t-1} + b_t, per channel. TPU adaptation: instead of a
sequential scan (hostile to the VPU) the sequence is tiled into (bt, wt)
VMEM blocks; within a block the recurrence closes in parallel via the
bounded decay matrix D[t,s,c] = exp(clip(L_{t-1..t}-L_s)) (<= 1, no
under/overflow), and a [1, wt] VMEM scratch carries the state across time
blocks (grid dim 2, sequential).

Grid: (B, n_w_tiles, n_t_blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, carry_sc, *, bt: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        carry_sc[...] = h0_ref[...].astype(jnp.float32)  # [1, wt]

    a = a_ref[0].astype(jnp.float32)  # [bt, wt]
    b = b_ref[0].astype(jnp.float32)
    log_a = jnp.log(jnp.maximum(a, 1e-37))
    L = jnp.cumsum(log_a, axis=0)  # L_t = sum_{u<=t} log a_u  (inclusive)
    # h_t = exp(L_t) * h_in + sum_{s<=t} exp(L_t - L_s) * b_s
    diff = L[:, None, :] - L[None, :, :]  # [t, s, wt]
    mask = (jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1))
    D = jnp.where(mask[..., None], jnp.exp(jnp.clip(diff, -60.0, 0.0)), 0.0)
    h = jnp.einsum("tsw,sw->tw", D, b) + jnp.exp(L) * carry_sc[...]
    o_ref[0] = h.astype(o_ref.dtype)
    carry_sc[...] = h[-1:, :]


def rglru_scan_kernel(a, b, h0, *, block_t: int = 64, block_w: int = 512,
                      interpret: bool = False):
    """a, b: [B, S, W] (f32); h0: [B, W]. Returns h: [B, S, W] f32."""
    B, S, W = a.shape
    bt = min(block_t, S)
    wt = min(block_w, W)
    assert S % bt == 0 and W % wt == 0
    kern = functools.partial(_rglru_kernel, bt=bt)
    return pl.pallas_call(
        kern,
        grid=(B, W // wt, S // bt),
        in_specs=[
            pl.BlockSpec((1, bt, wt), lambda b_, w_, t_: (b_, t_, w_)),
            pl.BlockSpec((1, bt, wt), lambda b_, w_, t_: (b_, t_, w_)),
            pl.BlockSpec((1, wt), lambda b_, w_, t_: (b_, w_)),
        ],
        out_specs=pl.BlockSpec((1, bt, wt), lambda b_, w_, t_: (b_, t_, w_)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, wt), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b, h0)
