"""Operations and bytes from shapes: the yardstick for utilisation and
roofline shares. Only real rows count: a decode row of a running request,
or a prompt position a prefill lane computes. Padding rows, masked lanes
and prefix-cache hits count nothing, so a program that stops computing
them is credited and one that computes them shows a lower share.

A row attends over `keys` positions: the row that emits a request's i-th
output token (i = 0 is the last prompt position) sits at position
plen - 1 + i and attends over plen + i keys; a prompt position p attends
over p + 1.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int = 2  # bf16 paged K/V
    q_bytes: int = 2  # bf16 queries into the paged kernel
    out_bytes: int = 4  # f32 kernel output
    logit_bytes: int = 4  # f32 logits into the sampling mask

    @classmethod
    def from_config(cls, hf: dict) -> "Dims":
        return cls(layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
                   n_heads=hf["num_attention_heads"],
                   n_kv_heads=hf["num_key_value_heads"],
                   head_dim=hf["head_dim"], d_ff=hf["intermediate_size"],
                   vocab=hf["vocab_size"])

    @property
    def matmul_params(self) -> int:
        """Weights one token multiplies through: q, k, v, o, gate, up and
        down in every layer, and the output head (the embedding is only
        gathered)."""
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd * 2 + d * self.n_kv_heads * hd * 2
        return self.layers * (attn + 3 * d * self.d_ff) + d * self.vocab


def attn_flops(dims: Dims, keys: int) -> int:
    """Scores and weighted values of one row over `keys` positions, all
    layers."""
    return 4 * dims.layers * dims.n_heads * dims.head_dim * keys


def row_flops(dims: Dims, keys: int) -> int:
    """Model operations of one real row: 2 per matmul weight plus
    attention at its context."""
    return 2 * dims.matmul_params + attn_flops(dims, keys)


def paged_attn_bytes(dims: Dims, seq_keys: Iterable[int], n_rows: int) -> int:
    """Least HBM traffic of the paged-decode kernel in one step: each real
    sequence's K and V read once per layer at its deepest row's context,
    and q in and the output out for each real row."""
    kv = sum(seq_keys) * 2 * dims.n_kv_heads * dims.head_dim * dims.kv_bytes
    qo = n_rows * dims.n_heads * dims.head_dim * (dims.q_bytes
                                                  + dims.out_bytes)
    return dims.layers * (kv + qo)


def mask_bytes(dims: Dims, n_rows: int) -> int:
    """Least HBM traffic of the sampling mask: the logits of the rows that
    sample, read once."""
    return n_rows * dims.vocab * dims.logit_bytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at the
    chip's peaks."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
