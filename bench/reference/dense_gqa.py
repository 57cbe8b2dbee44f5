"""Plain float32 reference of a dense decoder with grouped-query attention
(Qwen2 and Llama/Yi as published): token embedding, then per layer
RMSNorm -> attention with optional q/k/v bias and rotate-half RoPE ->
residual -> RMSNorm -> SwiGLU MLP -> residual, then a final RMSNorm and
the output head. Written from the published description, with no kernel,
cache or batching, and importing nothing of the system under test.

Weights are the benchmark's canonical dict (bench/lib/weights.py):
  embed [V, d]; lm_head [d, V] or absent when tied (then embed.T);
  final_norm [d] gain; layers: stacked over the layer axis
  ln1, ln2 [L, d] gains; wq [L, d, Hq*hd], wk/wv [L, d, Hkv*hd],
  wo [L, Hq*hd, d]; bq/bk/bv [L, *] when the config has attention bias;
  w_gate/w_up [L, d, ff], w_down [L, ff, d].
Every weight is upcast to float32 one layer at a time inside the layer
scan, so a model whose bf16 weights fill the chip still fits.

quant=True is the control: the same forward computed in int8, the step
below the bf16 the configurations state. Every matmul weight, the
embedding and the head are rounded to int8 with one absmax scale per
output channel, and every matmul input to int8 with one scale per token;
norms, softmax and residuals stay float32. A comparison that cannot tell
it from the program is too loose.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def rope(x, pos, theta):
    """x [S, H, hd]: rotate-half RoPE at integer positions pos [S]."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def int8_round(w, axis):
    """w rounded to int8 steps of absmax/127 taken along `axis` (the
    contraction axis, so one scale per output channel)."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0,
                    1e-30)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def layer(h, w, pos, hp, quant=False):
    """One decoder layer over a whole sequence h [S, d] (float32)."""
    def f(k):
        x = w[k].astype(jnp.float32)
        return int8_round(x, 0) if quant and k in MATMULS else x

    def act(x):  # a matmul's input
        return int8_round(x, -1) if quant else x
    S = h.shape[0]
    hq, hkv, hd = hp["n_heads"], hp["n_kv_heads"], hp["head_dim"]
    x = act(rms_norm(h, f("ln1"), hp["eps"]))
    q, k, v = x @ f("wq"), x @ f("wk"), x @ f("wv")
    if "bq" in w:
        q, k, v = q + f("bq"), k + f("bk"), v + f("bv")
    q = rope(q.reshape(S, hq, hd), pos, hp["theta"])
    k = rope(k.reshape(S, hkv, hd), pos, hp["theta"])
    v = v.reshape(S, hkv, hd)
    # query head j reads key/value head j // (hq // hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v).reshape(S, hq * hd)
    h = h + act(o) @ f("wo")
    x = act(rms_norm(h, f("ln2"), hp["eps"]))
    return h + act(jax.nn.silu(x @ f("w_gate")) * (x @ f("w_up"))) \
        @ f("w_down")


@partial(jax.jit, static_argnames=("hp_items", "quant"))
def _logits_at(weights, tokens, read_pos, hp_items, quant):
    hp = dict(hp_items)
    pos = jnp.arange(tokens.shape[0])
    h = weights["embed"][tokens].astype(jnp.float32)
    if quant:  # per-row scales of the table are per-row scales here
        h = int8_round(h, 1)

    def body(h, w):
        return layer(h, w, pos, hp, quant), None

    h, _ = jax.lax.scan(body, h, weights["layers"])
    h = rms_norm(h[read_pos], weights["final_norm"].astype(jnp.float32),
                 hp["eps"])
    head = (weights["lm_head"] if "lm_head" in weights
            else weights["embed"].T).astype(jnp.float32)
    if quant:
        head, h = int8_round(head, 0), int8_round(h, -1)
    return h @ head


def logits_at(weights, tokens, read_pos, hparams: dict, *,
              quant: bool = False):
    """Logits [len(read_pos), V] (float32) of the causal forward over the
    whole token sequence, read at positions read_pos. Padding tokens past
    the last position read change nothing (attention is causal)."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(weights, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(read_pos, jnp.int32),
                          tuple(sorted(hparams.items())), quant)


def hparams_of(hf: dict) -> dict:
    """The reference's hyperparameters from a config file's published
    keys."""
    return {"n_heads": hf["num_attention_heads"],
            "n_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"], "eps": hf["rms_norm_eps"],
            "theta": float(hf["rope_theta"])}
