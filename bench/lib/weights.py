"""Seeded random weights, made on the device in one jitted call, in the
type they are served in (bf16 matmul weights and biases, f32 norm gains).

The canonical dict (names as in bench/reference/dense_gqa.py) is what the
reference reads; program_params() lays the same arrays out as the program
under test expects them, sharing every large buffer. Values follow the
published initialisation: matmul weights and the embedding N(0, std),
biases N(0, bias_std), norm gains 1 + N(0, gain_jitter) (random rather than
all ones, so that a norm that ignored its gain would show in the check).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A PRNG key for any non-negative seed (also above 2**32)."""
    hi, lo = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray([hi, lo], jnp.uint32))


def shapes(hf: dict) -> dict:
    """Canonical leaf -> (shape, dtype, init kind)."""
    L, d, ff, V = (hf["num_hidden_layers"], hf["hidden_size"],
                   hf["intermediate_size"], hf["vocab_size"])
    hq, hkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    bf, f32 = jnp.bfloat16, jnp.float32
    layers = {"ln1": ((L, d), f32, "gain"), "ln2": ((L, d), f32, "gain"),
              "wq": ((L, d, hq * hd), bf, "w"),
              "wk": ((L, d, hkv * hd), bf, "w"),
              "wv": ((L, d, hkv * hd), bf, "w"),
              "wo": ((L, hq * hd, d), bf, "w"),
              "w_gate": ((L, d, ff), bf, "w"), "w_up": ((L, d, ff), bf, "w"),
              "w_down": ((L, ff, d), bf, "w")}
    if hf["attention_bias"]:
        layers.update(bq=((L, hq * hd), bf, "b"), bk=((L, hkv * hd), bf, "b"),
                      bv=((L, hkv * hd), bf, "b"))
    top = {"embed": ((V, d), bf, "w"), "final_norm": ((d,), f32, "gain")}
    if not hf["tie_word_embeddings"]:
        top["lm_head"] = ((d, V), bf, "w")
    return {"layers": layers, **top}


@partial(jax.jit, static_argnames=("spec", "std", "bias_std", "jitter"))
def _make(key, spec, std, bias_std, jitter):
    scale = {"w": std, "b": bias_std, "gain": jitter}
    out, i = {}, 0
    for group, leaves in spec:
        dst = out.setdefault(group, {}) if group else out
        for name, (shape, dtype, kind) in leaves:
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale[kind]
            dst[name] = (1.0 + x if kind == "gain" else x).astype(dtype)
            i += 1
    return out


def _frozen(sh: dict):
    layers = tuple(sorted(sh["layers"].items()))
    top = tuple(sorted((k, v) for k, v in sh.items() if k != "layers"))
    return (("layers", layers), (None, top))


def make(hf: dict, init: dict, seed: int) -> dict:
    """The canonical weights for one seed, on the default device."""
    return _make(jax_key(seed), _frozen(shapes(hf)), float(init["std"]),
                 float(init["bias_std"]), float(init["gain_jitter"]))


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
