"""Operations and bytes count real rows only, each layer at its own key
cap and with only the experts a token is routed to; every architecture
module's work obeys the same rules; the lane model follows the program's
counters."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench.lib import harness, readers, spec, traffic, work

DENSE = spec.module("arch", "dense_gqa")
DIMS = DENSE.work({"num_hidden_layers": 2, "hidden_size": 8,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 4, "intermediate_size": 16, "vocab_size": 32})
ARCHS = sorted(p.stem for p in (spec.BENCH / "arch").glob("*.py"))
FIX = Path(__file__).parent / "fixtures"


def test_matmul_params_and_row_flops():
    per_layer = 8 * 16 * 2 + 8 * 8 * 2 + 3 * 8 * 16
    assert DIMS.matmul_params == 2 * per_layer + 8 * 32
    assert DIMS.row_flops(10) == \
        2 * DIMS.matmul_params + 4 * 2 * 4 * 4 * 10


def test_paged_bytes_read_each_sequence_once():
    one = DIMS.paged_attn_bytes([100], 1)
    lane = DIMS.paged_attn_bytes([100], 64)  # 64 rows, one sequence
    kv = 100 * 2 * 2 * 4 * 2  # keys x (K, V) x heads x head_dim x bf16
    assert one == 2 * (kv + 4 * 4 * 6)
    assert lane - one == 2 * 63 * 4 * 4 * 6  # only q and out grow
    assert DIMS.mask_bytes(3) == 3 * 32 * 4


def test_a_window_layer_reads_at_most_its_window():
    full = work.Layer(n_heads=4, n_kv_heads=2, head_dim=4, matmul_params=0)
    win = work.Layer(n_heads=4, n_kv_heads=2, head_dim=4, matmul_params=0,
                     key_cap=64)
    d = work.Dims(layers=(win, win, win, full), head_params=0, vocab=32)
    per_key = 4 * 4 * 4  # 4 x heads x head_dim, one layer
    assert d.attn_flops(50) == 4 * per_key * 50
    assert d.attn_flops(1000) == 3 * per_key * 64 + per_key * 1000
    kv_per_key = 2 * 2 * 4 * 2  # (K, V) x kv heads x head_dim x bf16
    qo = 4 * 4 * 6  # one row's q in and output out, one layer
    assert d.paged_attn_bytes([1000, 10], 2) == \
        kv_per_key * (3 * (64 + 10) + 1000 + 10) + 4 * 2 * qo


def test_an_expert_layer_counts_the_routed_experts_only():
    d, ff, n_exp, top_k = 16, 8, 64, 8
    one = work.swiglu_params(d, ff)
    assert work.expert_params(d, ff, n_exp, top_k) == d * n_exp + 8 * one
    assert work.expert_params(d, ff, n_exp, top_k, n_shared=1) == \
        d * n_exp + 9 * one
    attn = work.attn_params(d, 4, 2, 4)
    moe = work.Layer(n_heads=4, n_kv_heads=2, head_dim=4,
                     matmul_params=attn + work.expert_params(d, ff, n_exp,
                                                             top_k))
    dims = work.Dims(layers=(moe,) * 2, head_params=d * 32, vocab=32)
    assert dims.matmul_params < 2 * (attn + n_exp * one)  # not all experts
    assert dims.row_flops(0) == 2 * (2 * (attn + d * n_exp + 8 * one)
                                     + d * 32)


def _smoke(key):
    return next(hf for p in sorted(FIX.glob("*.json"))
                if (hf := json.loads(p.read_text())).get("reference") == key)


@pytest.mark.parametrize("key", ARCHS)
def test_work_of_each_architecture_counts_rows_alike(key):
    """Whatever the layers, attention grows with the context and the
    matmuls do not; a lane's rows share one read of their sequence; the
    mask reads each sampling row's logits once."""
    hf = _smoke(key)
    d = spec.module("arch", key).work(hf)
    mm = d.row_flops(1) - d.attn_flops(1)
    assert mm > 0 and all(d.row_flops(k) - d.attn_flops(k) == mm
                          for k in (2, 100, 5000))
    assert 0 < d.attn_flops(1) <= d.attn_flops(100) <= d.attn_flops(5000)
    one, lane = d.paged_attn_bytes([300], 1), d.paged_attn_bytes([300], 65)
    assert lane - one == 64 * (d.paged_attn_bytes([1], 2)
                               - d.paged_attn_bytes([1], 1))
    assert d.mask_bytes(3) == 3 * hf["vocab_size"] * 4


def test_roofline_is_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000, 50, peak) == 10.0
    assert work.least_seconds(10, 500, peak) == 50.0


class FakeEngine:
    """Just what harness._account reads: a pool's cached prefix lengths
    and slots."""

    def __init__(self, cached):
        self.cached = cached
        self.pool = SimpleNamespace(
            cached_prefix_len=lambda slot: self.cached[slot])
        self.replica = SimpleNamespace(slot_of=lambda req: req.rid)


def _rec(rid, plen, gen=4):
    spec = traffic.RequestSpec(rid, 0.0, np.zeros(plen, np.int32), gen,
                               None, -1)
    req = SimpleNamespace(rid=rid, tokens=[], t_admit=None, done=False)
    return harness.Rec(spec, req, 0.0)


def test_lane_accounting_counts_real_rows():
    eng = FakeEngine({0: 16, 1: 0})
    a, b = _rec(0, 40), _rec(1, 20)
    lanes, sw = [], harness.SliceWork()
    # step 1: both admitted; 30 lane rows: a's 24 uncached, then 6 of b
    a.req.t_admit = b.req.t_admit = 0.0
    a.req.tokens.append(5)
    harness._account(eng, [a, b], lanes, 30, 1.0, DIMS, sw)
    keys = list(range(17, 41)) + list(range(1, 7))
    assert sw.rows == 30 and sw.consistent
    assert sw.flops == sum(DIMS.row_flops(k) for k in keys)
    assert sw.pd_bytes == DIMS.paged_attn_bytes([40, 6], 30)
    # step 2: a decodes its second token (41 keys), b's lane takes 14
    a.req.tokens.append(6)
    b.req.tokens.append(7)
    harness._account(eng, [a, b], lanes, 14, 2.0, DIMS, sw)
    assert sw.rows == 30 + 15 and sw.consistent and lanes == []
    assert a.token_times == [1.0, 2.0] and b.token_times == [2.0]


def test_lane_accounting_flags_a_mismatch():
    eng = FakeEngine({0: 0})
    a = _rec(0, 40)
    a.req.t_admit = 0.0
    sw = harness.SliceWork()
    a.req.tokens.append(1)  # a first token the counter cannot explain
    harness._account(eng, [a], [], 30, 1.0, DIMS, sw)
    assert not sw.consistent
    ctx = SimpleNamespace(window=SimpleNamespace(work=sw), summary=object(),
                          peak={"bf16_flops_per_s": 1.0})
    assert readers.step_mfu(ctx) is None


def test_device_readers_read_nothing_off_the_chip():
    sw = harness.SliceWork(flops=1e9, steps=3, rows=10)
    ctx = SimpleNamespace(window=SimpleNamespace(work=sw), summary=None,
                          peak=None)
    for r in (readers.step_mfu, readers.paged_decode_roofline,
              readers.sampling_mask_roofline, readers.device_idle_share):
        assert r(ctx) is None


# read from the work functions before they moved into bench/arch/: the
# matmul weights, row_flops(1000), attn_flops(1000), paged_attn_bytes(
# [100, 1200, 37], 576) and mask_bytes(3)
RECORDED = {"qwen2-1.5b": (1543569408, 3259170816, 172032000, 186970112,
                           1823232),
            "yi-9b-24l": (4414504960, 9222225920, 393216000, 405454848,
                          768000)}


@pytest.mark.parametrize("name", ["qwen2-1.5b", "yi-9b-24l"])
def test_work_dims_of_the_configurations(name):
    hf = spec.load_json(spec.BENCH / "configs" / f"{name}.json")
    d = spec.module("arch", hf["reference"]).work(hf)
    # the same count from the weights the benchmark makes: every matmul
    # weight, and the head (the embedding transposed when tied)
    sh = spec.module("reference", hf["reference"]).shapes(hf)
    mm = sum(int(np.prod(shape)) for k, (shape, _, kind) in
             sh["layers"].items() if kind == "w")
    assert d.matmul_params == mm + int(np.prod(sh["embed"][0]))
    assert (d.matmul_params, d.row_flops(1000), d.attn_flops(1000),
            d.paged_attn_bytes([100, 1200, 37], 576), d.mask_bytes(3)) \
        == RECORDED[name]
