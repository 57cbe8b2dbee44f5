"""jit'd wrapper for the RWKV-6 WKV Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rwkv6.kernel import wkv6_kernel


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, logw, u, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,logw: [B,S,H,hd] (model layout); u: [H,hd]."""
    rt, kt, vt, lt = (a.transpose(0, 2, 1, 3) for a in (r, k, v, logw))
    o, s_fin = wkv6_kernel(rt, kt, vt, lt, u, chunk=chunk, interpret=interpret)
    return o.transpose(0, 2, 1, 3), s_fin
