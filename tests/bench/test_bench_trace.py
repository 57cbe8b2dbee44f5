"""The reduction from a device trace to busy time, idle share, kernel time
by name and named idle gaps."""
from pathlib import Path

import pytest

from bench.lib import trace as T

FIXTURE = Path(__file__).parent / "fixtures" / "trace-chip.json"


def synthetic():
    ms = 1_000_000
    spans = [("admit+step", 0, 10 * ms), ("harness bookkeeping", 10 * ms,
                                           2 * ms),
             ("idle wait for arrival", 12 * ms, 8 * ms),
             ("admit+step", 20 * ms, 10 * ms)]
    ops = [("fusion.1", -2 * ms, 4 * ms),  # starts before the slice
           ("_pd_kernel", 2 * ms, 3 * ms),
           ("fusion.2", 4 * ms, 2 * ms),  # overlaps the kernel
           ("_mask_kernel", 8 * ms, 1 * ms),
           ("_pd_kernel", 21 * ms, 4 * ms),
           ("fusion.3", 29 * ms, 3 * ms)]  # runs past the slice
    return T.Trace(ops, spans)


def test_busy_is_the_union_of_op_intervals_inside_the_slice():
    s = T.reduce(synthetic())
    assert s.window_s == pytest.approx(30e-3)
    # [0,2] [2,6] [8,9] [21,25] [29,30]
    assert s.busy_s == pytest.approx(12e-3)
    assert s.idle_share == pytest.approx(1 - 12 / 30)


def test_kernel_time_by_name():
    s = T.reduce(synthetic())
    assert s.kernel_s("_pd_kernel") == pytest.approx(7e-3)
    assert s.kernel_s("_mask_kernel") == pytest.approx(1e-3)
    assert s.kernel_s("no_such_kernel") == 0.0
    assert s.top_ops(1) == [("_pd_kernel", pytest.approx(7e-3))]


def test_idle_gaps_are_named_by_the_host_span_they_fell_in():
    s = T.reduce(synthetic())
    # gaps: [6,8] step, [9,21] mostly idle wait (midpoint 15), [25,29] step
    assert s.idle_gaps[0] == ("idle wait for arrival", pytest.approx(12e-3))
    assert sorted(g for _, g in s.idle_gaps[1:]) == [
        pytest.approx(2e-3), pytest.approx(4e-3)]
    assert {n for n, _ in s.idle_gaps[1:]} == {"admit+step"}


def test_nothing_to_read_gives_none():
    assert T.reduce(T.Trace([], [("admit+step", 0, 5)])) is None
    assert T.reduce(T.Trace([("fusion", 0, 5)], [])) is None


def test_recorded_chip_trace():
    """One 576-row chat step of qwen2-1.5b on a TPU v5e: the paged-decode
    kernel holds the device for nearly all of it."""
    import json
    s = T.reduce(T.Trace.from_json(json.loads(FIXTURE.read_text())))
    assert s.window_s == pytest.approx(1.466548913)
    assert s.busy_s == pytest.approx(1.460852233)
    assert s.busy_s == pytest.approx(sum(s.op_s.values()))  # leaves only
    assert s.kernel_s("%paged_flash_decode") == pytest.approx(1.349798036)
    assert s.kernel_s("%topk_topp_mask") == pytest.approx(0.012296711)
    assert s.top_ops(1)[0][0].startswith("%paged_flash_decode")
    assert {n for n, _ in s.idle_gaps} <= set(T.SPANS)


def test_parent_ops_are_dropped():
    ops = [("%while.1", 0, 100), ("a", 0, 40), ("b", 50, 30), ("c", 120, 5)]
    assert T._leaves(ops) == [("a", 0, 40), ("b", 50, 30), ("c", 120, 5)]
