"""Public fused top-k/top-p mask op.

The serving decode step (launch/steps.py make_sample_fn) calls this inside
its jit: on TPU it lowers to the Pallas bisection kernel, elsewhere to the
sort-based XLA reference — the same keep-set semantics either way, so the
seeded-sampling reproducibility tests are meaningful on every backend
(interpret-mode Pallas is reserved for the kernel-vs-oracle tests; running
it in the CPU serving hot loop would pay interpreter overhead per step).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.sampling.kernel import topk_topp_mask_kernel
from repro.kernels.sampling.ref import topk_topp_mask_ref


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@partial(jax.jit, static_argnames=("impl",))
def topk_topp_mask(logits, top_k, top_p, *, impl: str | None = None):
    """logits [T,V]; top_k [T] int32 (<=0 off); top_p [T] f32 (>=1 off).

    Returns [T,V] f32: kept logits unchanged, dropped entries at NEG_INF.
    impl: "pallas" | "interpret" (Pallas in interpreter mode) | "xla";
    None picks pallas on TPU, xla elsewhere.
    """
    impl = impl or _default_impl()
    if impl == "pallas":
        return topk_topp_mask_kernel(logits, top_k, top_p, interpret=False)
    if impl == "interpret":
        return topk_topp_mask_kernel(logits, top_k, top_p, interpret=True)
    assert impl == "xla", impl
    return topk_topp_mask_ref(logits, top_k, top_p)
