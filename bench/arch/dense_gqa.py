"""How the program under test takes a dense decoder with grouped-query
attention (bench/reference/dense_gqa.py): its config, its parameter tree
over the canonical weights, and the work one real row does in it.

Every layer is the program's "attn" block (RMSNorm, attention with
optional q/k/v bias, RMSNorm, SwiGLU MLP), stacked; no layer has a window
or experts.
"""
from __future__ import annotations

import jax

from bench.lib import work as W
from repro.configs.base import ModelConfig


def model_config(hf: dict) -> ModelConfig:
    """The program's config for a configuration file's published keys."""
    return ModelConfig(
        name=hf["name"], family="dense", n_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"], head_dim=hf["head_dim"],
        qkv_bias=hf["attention_bias"], rope_theta=float(hf["rope_theta"]),
        norm_eps=hf["rms_norm_eps"], tie_embeddings=hf["tie_word_embeddings"],
        block_pattern=("attn",), source=hf["source"])


@jax.jit
def _derived(w):
    """Program-only leaves: zero-centred norm gains (the program applies
    1 + scale) and the output head as its own matrix."""
    out = {"ln1": w["layers"]["ln1"] - 1.0, "ln2": w["layers"]["ln2"] - 1.0,
           "final_norm": w["final_norm"] - 1.0}
    if "lm_head" not in w:  # tied: the program holds the head separately
        out["unembed"] = w["embed"].T
    return out


def program_params(w: dict, hf: dict, vocab_padded: int) -> dict:
    """The program's parameter tree over the canonical arrays."""
    if vocab_padded != hf["vocab_size"]:
        raise ValueError("the benchmark's configurations have vocabularies "
                         "the program does not pad")
    dv = _derived(w)
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in lw}
    block = {"ln1": dv["ln1"], "attn": attn, "ln2": dv["ln2"],
             "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": w["embed"], "blocks": (block,), "tail": (),
            "final_norm": dv["final_norm"],
            "unembed": w.get("lm_head", dv.get("unembed"))}


def work(hf: dict) -> W.Dims:
    """Every layer: q, k, v, o and a SwiGLU MLP, attending over the whole
    context; then the output head."""
    d, hq, hkv, hd = (hf["hidden_size"], hf["num_attention_heads"],
                      hf["num_key_value_heads"], hf["head_dim"])
    layer = W.Layer(n_heads=hq, n_kv_heads=hkv, head_dim=hd,
                    matmul_params=W.attn_params(d, hq, hkv, hd)
                    + W.swiglu_params(d, hf["intermediate_size"]))
    return W.Dims(layers=(layer,) * hf["num_hidden_layers"],
                  head_params=d * hf["vocab_size"], vocab=hf["vocab_size"])
