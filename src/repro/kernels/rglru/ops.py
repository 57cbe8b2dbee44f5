"""jit'd wrapper for the RG-LRU Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.rglru.kernel import rglru_scan_kernel


@partial(jax.jit, static_argnames=("block_t", "block_w", "interpret"))
def rglru_scan(a, b, h0=None, *, block_t: int = 64, block_w: int = 512,
               interpret: bool = False):
    """Linear recurrence h_t = a_t h_{t-1} + b_t. a,b: [B,S,W]."""
    if h0 is None:
        h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    return rglru_scan_kernel(a.astype(jnp.float32), b.astype(jnp.float32),
                             h0.astype(jnp.float32), block_t=block_t,
                             block_w=block_w, interpret=interpret)
