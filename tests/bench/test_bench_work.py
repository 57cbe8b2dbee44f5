"""Operations and bytes count real rows only, and the lane model follows
the program's counters."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench.lib import harness, readers, traffic, work

DIMS = work.Dims(layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=4,
                 d_ff=16, vocab=32)


def test_matmul_params_and_row_flops():
    per_layer = 8 * 16 * 2 + 8 * 8 * 2 + 3 * 8 * 16
    assert DIMS.matmul_params == 2 * per_layer + 8 * 32
    assert work.row_flops(DIMS, 10) == \
        2 * DIMS.matmul_params + 4 * 2 * 4 * 4 * 10


def test_paged_bytes_read_each_sequence_once():
    one = work.paged_attn_bytes(DIMS, [100], 1)
    lane = work.paged_attn_bytes(DIMS, [100], 64)  # 64 rows, one sequence
    kv = 100 * 2 * 2 * 4 * 2  # keys x (K, V) x heads x head_dim x bf16
    assert one == 2 * (kv + 4 * 4 * 6)
    assert lane - one == 2 * 63 * 4 * 4 * 6  # only q and out grow
    assert work.mask_bytes(DIMS, 3) == 3 * 32 * 4


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
    assert sw.flops == sum(work.row_flops(DIMS, k) for k in keys)
    assert sw.pd_bytes == work.paged_attn_bytes(DIMS, [40, 6], 30)
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


@pytest.mark.parametrize("name", ["qwen2-1.5b", "yi-9b-24l"])
def test_work_dims_of_the_configurations(name):
    from bench.lib import spec
    hf = spec.load_json(spec.BENCH / "configs" / f"{name}.json")
    d = work.Dims.from_config(hf)
    # the same count from the weights the benchmark makes: every matmul
    # weight, and the head (the embedding transposed when tied)
    from bench.lib import weights
    sh = weights.shapes(hf)
    mm = sum(int(np.prod(shape)) for k, (shape, _, kind) in
             sh["layers"].items() if kind == "w")
    assert d.matmul_params == mm + int(np.prod(sh["embed"][0]))
