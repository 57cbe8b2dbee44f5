"""jit'd public wrapper around the flash attention kernel.

Accepts the model's [B,S,H,hd] layout, handles GQA head mapping, and
picks hardware-aligned block sizes. Runs the Pallas interpreter only
when the caller passes interpret=True.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention.kernel import flash_attention_kernel


@partial(jax.jit, static_argnames=("causal", "window", "n_kv_heads",
                                   "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    n_kv_heads: int | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: [B,Sq,Hq,hd], k/v: [B,Sk,Hkv,hd] -> [B,Sq,Hq*hd] (model layout)."""
    B, Sq, Hq, hd = q.shape
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_kernel(qt, kt, vt, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return o.transpose(0, 2, 1, 3).reshape(B, Sq, Hq * hd)
