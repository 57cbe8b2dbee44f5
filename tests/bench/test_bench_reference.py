"""The plain float32 reference agrees with the program's forward at smoke
size, and would not agree with a program that departs from it."""
import dataclasses
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import harness, spec, weights
from repro.configs.base import ParallelPlan
from repro.models import model as Mo
from repro.models.env import Env

HF = json.loads((Path(__file__).parent / "fixtures"
                 / "smoke-config.json").read_text())
REF, ARCH = (spec.module(k, HF["reference"]) for k in ("reference", "arch"))
ENV = Env(None, ParallelPlan(fsdp=False, remat="full", attn_impl="naive",
                             kv_cache="replicated"))
# the program computes in bf16: its logits sit within a few percent of
# the float32 reference's spread (measured 5-6% at this size); a wrong
# RoPE base, norm epsilon or missing bias moves them by whole spreads
REL_TOL = 0.15


def _rel_err(seed, mcfg=None, hf=HF):
    w = weights.make(REF.shapes(hf), hf["weights"], seed)
    mcfg = mcfg or ARCH.model_config(hf)
    params = harness.program_params(ARCH, ARCH.model_config(hf), hf, w)
    toks = np.random.default_rng(seed).integers(
        0, hf["vocab_size"], size=(64,)).astype(np.int32)
    got, _, _ = Mo.forward(params, jnp.asarray(toks)[None], mcfg, ENV,
                           mode="train")
    got = np.asarray(got[0, :, :hf["vocab_size"]], np.float32)
    ref = np.asarray(REF.logits_at(w, toks, np.arange(64),
                                   REF.hparams_of(hf)))
    return float(np.abs(got - ref).max() / ref.std())


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_reference_matches_the_program(seed):
    assert _rel_err(seed) < REL_TOL


@pytest.mark.parametrize("change", [{"rope_theta": 1e5}, {"norm_eps": 1e-2}])
def test_reference_rejects_a_program_that_departs(change):
    mcfg = dataclasses.replace(ARCH.model_config(HF), **change)
    assert _rel_err(1, mcfg) > 5 * REL_TOL


def test_reference_reads_the_head_as_tied_or_not():
    untied = dict(HF, tie_word_embeddings=False)
    assert _rel_err(3, hf=untied) < REL_TOL
    w = weights.make(REF.shapes(untied), untied["weights"], 3)
    assert "lm_head" in w and "lm_head" not in weights.make(
        REF.shapes(HF), HF["weights"], 3)


def test_weights_are_seeded_and_in_the_served_types():
    a = weights.make(REF.shapes(HF), HF["weights"], 2**33 + 1)
    b = weights.make(REF.shapes(HF), HF["weights"], 2**33 + 1)
    c = weights.make(REF.shapes(HF), HF["weights"], 1)
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])
    assert a["embed"].dtype == jnp.bfloat16
    assert a["layers"]["ln1"].dtype == jnp.float32
    assert abs(float(jnp.std(a["layers"]["wq"].astype(jnp.float32)))
               - HF["weights"]["std"]) < 0.01
