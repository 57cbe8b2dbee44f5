"""Public flash-decoding ops: single-shard kernel + TP-sharded cache merge."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_decode.kernel import flash_decode_kernel, merge_partials


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q, k, v, cur_len, *, block_k: int = 512,
                 interpret: bool = False):
    """Unsharded decode attention. q [B,Hq,hd]; k,v [B,Hkv,S,hd]."""
    o, _, _ = flash_decode_kernel(q, k, v, cur_len, block_k=block_k,
                                  interpret=interpret)
    return o.astype(q.dtype)


def flash_decode_seq_sharded(mesh, tp_axis: str, q, k, v, cur_len, *,
                             block_k: int = 512, interpret: bool = False):
    """Flash-decoding over a cache whose seq dim is sharded over `tp_axis`.

    Each shard runs the kernel on its slice; partials merge with LSE weights
    (collective = one pmax + two psums of [B,Hq,hd] — tiny vs the cache read,
    which is the point of the layout).
    """
    S = k.shape[2]
    tp = mesh.shape[tp_axis]
    s_local = S // tp

    def body(q_l, k_l, v_l):
        idx = jax.lax.axis_index(tp_axis)
        local_len = jnp.clip(cur_len - idx * s_local, -1, s_local - 1)
        o, m, l = flash_decode_kernel(q_l, k_l, v_l, local_len,
                                      block_k=min(block_k, s_local),
                                      interpret=interpret)
        # an all-masked shard produces l=0 -> zero weight in the merge
        return merge_partials(o, m, l, tp_axis)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(), P(None, None, tp_axis, None),
                             P(None, None, tp_axis, None)),
                   out_specs=P(), check_rep=False)
    return fn(q, k, v).astype(q.dtype)
