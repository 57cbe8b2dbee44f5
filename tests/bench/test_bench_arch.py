"""The architecture modules: a configuration's `reference` key picks
bench/reference/<key>.py and bench/arch/<key>.py; the weights they lay out
are the ones recorded before the split; and for every architecture module,
a prompt run through the program's paged cache in 512-row lanes and then
decoded through it agrees with the module's plain reference, where the
int8 control does not."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import harness, spec, weights
from repro.models import model as Mo
from repro.models.env import Env
from repro.serve import SERVE_PLAN

FIX = Path(__file__).parent / "fixtures"
ARCHS = sorted(p.stem for p in (spec.BENCH / "arch").glob("*.py"))
# the widths every pinned configuration is drawn at: its own layout (bias,
# tied head, init), the smoke fixture's sizes
SMOKE = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 512}
LANE = 512  # rows of one prefill lane, as the cells' engines run them
BLOCK = 16
PROMPT, GEN = 600, 24  # two lane steps (512 + 88 rows), then 24 decode rows
# rms of (program - reference) logits over the reference's standard
# deviation, taken over the lane rows and over the decode rows apart. The
# program computes in bf16 (weights, activations, KV); at the smoke size
# it reads 0.0076-0.0119 in either part (CPU, seeds 1-3, 7, 11-18,
# 2**31 + 5), the int8 control (W8A8, bench/reference) 0.0248-0.0345.
# A wrong position, a missed key or a stale block moves whole spreads.
RMS_TOL = 0.018


def smoke_config(key: str) -> dict:
    """The smoke-size configuration file of an architecture key: the
    first fixture naming it (each architecture module brings one)."""
    for p in sorted(FIX.glob("*.json")):
        hf = json.loads(p.read_text())
        if hf.get("reference") == key:
            return hf
    raise LookupError(f"no fixture under {FIX} names reference {key!r}")


def test_every_arch_module_has_its_reference_and_a_smoke_config():
    refs = sorted(p.stem for p in (spec.BENCH / "reference").glob("*.py"))
    assert ARCHS and ARCHS == refs
    for key in ARCHS:
        ref, arch = (spec.module(k, key) for k in ("reference", "arch"))
        for f in ("shapes", "hparams_of", "logits_at"):
            assert callable(getattr(ref, f))
        for f in ("model_config", "program_params", "work"):
            assert callable(getattr(arch, f))
        smoke_config(key)


def test_an_unknown_reference_key_names_the_missing_file(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    hf = dict(smoke_config(ARCHS[0]), reference="no_such_arch")
    (tmp_path / "bench/configs/x.json").write_text(json.dumps(hf))
    bench = {"configs": [{"name": "x", "file": "bench/configs/x.json"}],
             "workloads": [{"name": "x.y", "config": "x", "traffic": "y",
                            "chips": 1}],
             "end_to_end": [], "per_layer": []}
    with pytest.raises(FileNotFoundError,
                       match="bench/reference/no_such_arch.py"):
        spec.find_cell("x.y", root=tmp_path, bench=bench)
    with pytest.raises(FileNotFoundError, match="bench/arch/no_such_arch.py"):
        spec.module("arch", "no_such_arch", root=tmp_path)


PINS = json.loads((FIX / "weight-pins.json").read_text())


@pytest.mark.parametrize("name", sorted(PINS["leaves"]))
def test_weights_are_the_recorded_ones(name):
    hf = dict(spec.load_json(spec.BENCH / "configs" / f"{name}.json"),
              **SMOKE)
    ref = spec.module("reference", hf["reference"])
    w = weights.make(ref.shapes(hf), hf["weights"], PINS["seed"])
    got = {"/".join(str(k.key) for k in path):
           hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
           for path, x in jax.tree_util.tree_leaves_with_path(w)}
    assert got == PINS["leaves"][name]


def _served_and_reference(key: str, seed: int):
    """Logits of every position of a PROMPT-token prompt run through the
    program's paged cache in LANE-row lanes, then GEN greedy tokens
    decoded through it; and the reference's logits (float32 and the int8
    control) over the same tokens."""
    hf = smoke_config(key)
    ref, arch = (spec.module(k, key) for k in ("reference", "arch"))
    V = hf["vocab_size"]
    w = weights.make(ref.shapes(hf), hf["weights"], seed)
    mcfg = arch.model_config(hf)
    env = Env(None, SERVE_PLAN)
    params = harness.program_params(arch, mcfg, hf, w)
    n = PROMPT + GEN
    nb = -(-n // BLOCK)
    cache = Mo.init_paged_cache(mcfg, env, 1, nb + 1, BLOCK)  # 0: null
    table = np.arange(1, nb + 1, dtype=np.int32)

    @jax.jit
    def step(p, c, tok, cur_len, tables):
        lg, c, _ = Mo.forward(p, tok[:, None], mcfg, env, mode="decode",
                              caches=c, cur_len=cur_len,
                              block_tables={"global": tables})
        return lg[:, 0, :V].astype(jnp.float32), c

    prompt = np.random.default_rng(seed).integers(
        0, V, size=(PROMPT,)).astype(np.int32)
    out = []
    for s in range(0, PROMPT, LANE):  # padding rows: depth 0, null table
        rows = np.arange(s, min(s + LANE, PROMPT))
        tok = np.zeros(LANE, np.int32)
        cur = np.zeros(LANE, np.int32)
        tbl = np.zeros((LANE, nb), np.int32)
        tok[:len(rows)], cur[:len(rows)], tbl[:len(rows)] = \
            prompt[rows], rows, table
        lg, cache = step(params, cache, tok, cur, tbl)
        out.append(np.asarray(lg)[:len(rows)])
    seq = list(prompt)
    for pos in range(PROMPT, n):
        seq.append(int(out[-1][-1].argmax()))
        lg, cache = step(params, cache, np.asarray(seq[-1:], np.int32),
                         np.asarray([pos], np.int32), table[None])
        out.append(np.asarray(lg))
    seq = np.asarray(seq, np.int32)
    hp = ref.hparams_of(hf)
    f32 = np.asarray(ref.logits_at(w, seq, np.arange(n), hp))
    int8 = np.asarray(ref.logits_at(w, seq, np.arange(n), hp, quant=True))
    return np.concatenate(out), f32, int8


def _rms(got, want):
    """The relative rms error of the lane rows and of the decode rows."""
    e = (got - want) / want.std()
    return [float(np.sqrt(np.mean(x * x))) for x in (e[:PROMPT], e[PROMPT:])]


@pytest.mark.parametrize("key", ARCHS)
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_prefill_lanes_then_decode_match_the_reference(key, seed):
    prog, f32, int8 = _served_and_reference(key, seed)
    assert max(_rms(prog, f32)) < RMS_TOL
    assert min(_rms(int8, f32)) > RMS_TOL  # the control fails both parts
