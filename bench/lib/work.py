"""Operations and bytes from shapes: the yardstick for utilisation and
roofline shares. Only real rows count: a decode row of a running request,
or a prompt position a prefill lane computes. Padding rows, masked lanes
and prefix-cache hits count nothing, so a program that stops computing
them is credited and one that computes them shows a lower share.

A row attends over `keys` positions: the row that emits a request's i-th
output token (i = 0 is the last prompt position) sits at position
plen - 1 + i and attends over plen + i keys; a prompt position p attends
over p + 1. A window layer attends over at most its window of them.

A model is a stack of Layer rows, one per layer, that its architecture
module (bench/arch/<key>.py, `work(hf)`) builds from the configuration:
each layer's attention heads, its key cap, and the matmul weights one
token multiplies through in it (of an expert layer, the router and the
experts a token is routed to, not all of them).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Optional, Tuple


@dataclass(frozen=True)
class Layer:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    matmul_params: int  # weights one token multiplies through here
    key_cap: Optional[int] = None  # a window layer's window

    def keys(self, keys: int) -> int:
        """The keys this layer's row reads at a context of `keys`."""
        return keys if self.key_cap is None else min(keys, self.key_cap)


@dataclass(frozen=True)
class Dims:
    layers: Tuple[Layer, ...]
    head_params: int  # the output head (the embedding is only gathered)
    vocab: int
    kv_bytes: int = 2  # bf16 paged K/V
    q_bytes: int = 2  # bf16 queries into the paged kernel
    out_bytes: int = 4  # f32 kernel output
    logit_bytes: int = 4  # f32 logits into the sampling mask

    @property
    def matmul_params(self) -> int:
        """Weights one token multiplies through: every layer's and the
        output head."""
        return sum(ly.matmul_params for ly in self.layers) + self.head_params

    def attn_flops(self, keys: int) -> int:
        """Scores and weighted values of one row over `keys` positions,
        all layers, each at its own key cap."""
        return sum(4 * ly.n_heads * ly.head_dim * ly.keys(keys)
                   for ly in self.layers)

    def row_flops(self, keys: int) -> int:
        """Model operations of one real row: 2 per matmul weight plus
        attention at its context."""
        return 2 * self.matmul_params + self.attn_flops(keys)

    def paged_attn_bytes(self, seq_keys: Iterable[int], n_rows: int) -> int:
        """Least HBM traffic of the paged-decode kernel in one step: each
        real sequence's K and V read once per layer at its deepest row's
        context (capped at the layer's window), and q in and the output
        out for each real row."""
        seq_keys = list(seq_keys)
        total = 0
        for ly in self.layers:
            kv = sum(ly.keys(k) for k in seq_keys) * 2 * ly.n_kv_heads \
                * ly.head_dim * self.kv_bytes
            qo = n_rows * ly.n_heads * ly.head_dim * (self.q_bytes
                                                      + self.out_bytes)
            total += kv + qo
        return total

    def mask_bytes(self, n_rows: int) -> int:
        """Least HBM traffic of the sampling mask: the logits of the rows
        that sample, read once."""
        return n_rows * self.vocab * self.logit_bytes


def attn_params(d_model: int, n_heads: int, n_kv_heads: int,
                head_dim: int) -> int:
    """q, k, v and o projections of one attention layer."""
    return d_model * n_heads * head_dim * 2 + d_model * n_kv_heads \
        * head_dim * 2


def swiglu_params(d_model: int, d_ff: int) -> int:
    """gate, up and down of one SwiGLU MLP."""
    return 3 * d_model * d_ff


def expert_params(d_model: int, d_expert: int, n_experts: int, top_k: int,
                  n_shared: int = 0) -> int:
    """Weights one token multiplies through in an expert layer of SwiGLU
    experts: the router over all experts, then its top_k routed experts
    and any shared ones."""
    return d_model * n_experts + (top_k + n_shared) \
        * swiglu_params(d_model, d_expert)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at the
    chip's peaks."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
