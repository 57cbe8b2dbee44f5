"""The control at a size the CPU holds: the reference computed in int8
(W8A8, the step below the configurations' bf16) reads a mean logit gap
that the limit fails, where the program's bf16 forward passes it.

The size is qwen2's layout at 256 wide and 4 layers, with init std 0.05
so that activations have the spread std 0.02 gives at 1536 wide. Readings
at this size (CPU, seeds 1-4, 256 positions): program mean gap 0.00038 to
0.00061, control 0.00235 to 0.00609; widest gap program 0.026-0.052,
control 0.080-0.136, which does not separate by 3x. The mean mask gap of
tokens sampled at temperature 0.7, top-k 50, top-p 0.9 (CPU, seeds 1-6):
program 0 to 0.00021, control 0.00092 to 0.00152.
"""
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import check, harness, weights
from bench.lib.traffic import Sampling
from bench.reference import dense_gqa
from repro.configs.base import ParallelPlan
from repro.models import model as Mo
from repro.models.env import Env

HF = dict(json.loads((Path(__file__).parent / "fixtures"
                      / "smoke-config.json").read_text()),
          hidden_size=256, num_hidden_layers=4, intermediate_size=768,
          vocab_size=2048, head_dim=64, num_attention_heads=4,
          num_key_value_heads=1)
HF["weights"] = dict(HF["weights"], std=0.05)
ENV = Env(None, ParallelPlan(fsdp=False, remat="full", attn_impl="naive",
                             kv_cache="replicated"))
S = 256
# between the program's highest reading and the control's lowest
MEAN_GAP_LIMIT = 0.0015
MEAN_MASK_GAP_LIMIT = 0.0005
CHAT = Sampling(temperature=0.7, top_p=0.9, top_k=50, seed=0)


def _logits(seed):
    """The reference's, the control's and the program's logits at every
    position of one random sequence."""
    w = weights.make(HF, HF["weights"], seed)
    mcfg = harness.model_config(HF)
    params = harness.program_params(mcfg, HF, w)
    toks = np.random.default_rng(seed).integers(
        0, HF["vocab_size"], size=(S,)).astype(np.int32)
    hp = dense_gqa.hparams_of(HF)
    ref = np.asarray(dense_gqa.logits_at(w, toks, np.arange(S), hp))
    q = np.asarray(dense_gqa.logits_at(w, toks, np.arange(S), hp,
                                       quant=True))
    prog, _, _ = Mo.forward(params, jnp.asarray(toks)[None], mcfg, ENV,
                            mode="train")
    prog = np.asarray(prog[0, :, :HF["vocab_size"]], np.float32)
    return ref, q, prog


def _gaps(seed):
    ref, q, prog = _logits(seed)
    best, rows = ref.max(-1), np.arange(S)
    return ([(best - ref[rows, prog.argmax(-1)]).tolist()],
            [(best - ref[rows, q.argmax(-1)]).tolist()])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_fails_the_mean_gap_limit(seed):
    prog, ctrl = _gaps(seed)
    p, c = check.statistics(prog), check.statistics(ctrl)
    assert p["mean_logit_gap"] <= MEAN_GAP_LIMIT < c["mean_logit_gap"]
    assert c["mean_logit_gap"] > 3 * p["mean_logit_gap"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_fails_the_mask_gap_limit(seed):
    ref, q, prog = _logits(seed)
    rng = np.random.default_rng(seed)
    edge = np.asarray(check.kept_threshold(
        jnp.asarray(ref), CHAT.temperature, CHAT.top_k, CHAT.top_p))
    rows = np.arange(S)
    p, c = (check.statistics(
        [np.maximum(edge - ref[rows, check.sample_kept(x, CHAT, rng)],
                    0.0).tolist()], "sampled")["mean_mask_gap"]
        for x in (prog, q))
    assert p <= MEAN_MASK_GAP_LIMIT < c
    assert c > 3 * p
