"""The readers of the engine's spans, counters and request stamps
(bench/lib/engine_spans.py) on a synthetic run: a span ring recorded
under a ManualClock, a device trace on another clock, and the requests'
phase stamps. Alignment of the two clocks succeeds, fails (None) and is
ambiguous (None); a ring that no longer reaches the window's start, a
program without spans and a run off the TPU read None."""
import pytest

from bench.lib import engine_spans as E
from bench.lib import harness, result
from bench.lib import trace as T
from repro.core.clock import ManualClock
from repro.core.spans import SpanRecorder
from repro.serve import Request

W0, W1 = 100.0, 120.0  # the window; the traced slice starts at 107
NS = 1_000_000_000
OFF_NS = 1_700_000_000 * NS  # the trace's clock minus the engine's
PEAK = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
METRICS = ("lane_wait_p95_ms", "prefill_p95_ms", "step_host_ms",
           "step_device_wait_ms", "row_fill_share", "paged_grid_live_share",
           "window_compile_s", "device_idle_engine_share")


def _wait_s(k: int, uniform: bool) -> float:
    """Every third step carries a lane: its transfer waits longer."""
    return 0.35 if uniform or k % 3 else 0.65


def record(first=90, last=119, uniform=False, maxlen=1 << 14):
    """One step a second from `first` to `last`: admit 10 ms, pack 10 ms,
    dispatch 30 ms, the transfer, retire 50 ms, snapshot 50 ms."""
    rec, clock = SpanRecorder(maxlen), ManualClock(float(first))
    for k in range(first, last + 1):
        clock.advance(k - clock.now())
        with rec.span("serve.step", clock) as st:
            with rec.span("serve.admit", clock):
                clock.advance(0.01)
            with rec.span("serve.decode", clock) as dec:
                with rec.span("serve.pack", clock):
                    clock.advance(0.01)
                dec.attrs = {"rows": 10, "real_rows": 6, "grid_cells": 30,
                             "grid_live": 9}
                with rec.span("serve.dispatch", clock):
                    clock.advance(0.03)
                with rec.span("serve.device_wait", clock):
                    clock.advance(_wait_s(k, uniform))
                with rec.span("serve.retire", clock):
                    clock.advance(0.05)
            with rec.span("serve.snapshot", clock):
                clock.advance(0.05)
            st.attrs = {"compile_s": 0.25 if k == 110 else 0.0}
    return rec


def trace_of(rec, nudge_s=0.0):
    """The profile the harness would keep of the slice: one admit+step
    around each serve.step from 107 on (4 us longer), harness
    bookkeeping after it, and the device busy from 20 ms into the
    dispatch to the end of the transfer."""
    ns = lambda t: OFF_NS + int(round(t * NS))
    spans, ops = [], []
    for i, st in enumerate(s for s in rec.spans("serve.step")
                           if 107 <= s.start < 113):
        nudge = nudge_s if i == 2 else 0.0
        a, b = st.start, st.end + 4e-6 + nudge
        spans.append(("admit+step", ns(a), ns(b) - ns(a)))
        spans.append(("harness bookkeeping", ns(b), ns(st.start + 0.9)
                      - ns(b)))
        disp, = [c for d in rec.children(st) if d.name == "serve.decode"
                 for c in rec.children(d) if c.name == "serve.dispatch"]
        wait, = [c for d in rec.children(st) if d.name == "serve.decode"
                 for c in rec.children(d) if c.name == "serve.device_wait"]
        ops.append(("fusion", ns(disp.start + 0.02),
                    ns(wait.end) - ns(disp.start + 0.02)))
    return T.Trace(ops, spans)


def _rec(rid, adm, lane, first):
    r = Request(rid=rid, prompt=[1, 2, 3], gen_len=4)
    r.t_admit, r.t_lane_start, r.t_first_token = adm, lane, first
    return harness.Rec(spec=None, req=r, due=adm - 0.5)


RECS = [_rec(0, 101.0, 101.2, 102.0),   # in the window
        _rec(1, 99.0, 100.5, 101.5),    # admitted before it: lane only
        _rec(2, 118.0, 119.5, None),    # no first token by the close
        _rec(3, 119.8, None, None)]     # no lane by the close


def ctx_of(rec, tr, *, peak=PEAK, monkeypatch):
    monkeypatch.setattr(E, "recorder", lambda: rec)
    win = harness.Window(W0, W1, RECS, [], {}, harness.SliceWork(), tr, 0, 1)
    return result.Ctx(win, T.reduce(tr) if tr else None, peak, None, {})


def test_the_readers_on_an_aligned_run(monkeypatch):
    rec = record()
    ctx = ctx_of(rec, trace_of(rec), monkeypatch=monkeypatch)
    # admitted in the window: 0.2 s, 1.5 s (at the close), 0.2 s
    assert E.lane_wait_p95_ms(ctx) == pytest.approx(1e3 * (0.2 + 0.9 * 1.3))
    # lanes that started in it: 0.8 s, 1.0 s, 0.5 s (at the close)
    assert E.prefill_p95_ms(ctx) == pytest.approx(1e3 * (0.8 + 0.9 * 0.2))
    steps = range(100, 120)
    waits = [_wait_s(k, False) for k in steps]
    assert E.step_device_wait_ms(ctx) == pytest.approx(
        1e3 * sum(waits) / len(waits))
    assert E.step_host_ms(ctx) == pytest.approx(1e3 * 0.15)
    assert E.row_fill_share(ctx) == pytest.approx(60.0)
    assert E.paged_grid_live_share(ctx) == pytest.approx(30.0)
    assert E.window_compile_s(ctx) == pytest.approx(0.25)
    # idle per step: admit, pack and 20 ms of dispatch before the device
    # runs, retire and snapshot after it: 0.04 + 0.1 s, all in engine
    # spans; the bookkeeping between steps is outside them
    window_s, by_name = E.idle_by_span(ctx)
    assert window_s == pytest.approx(ctx.summary.window_s)
    n = 6
    assert by_name["serve.admit"] == pytest.approx(n * 0.01, abs=1e-7)
    assert by_name["serve.pack"] == pytest.approx(n * 0.01, abs=1e-7)
    assert by_name["serve.dispatch"] == pytest.approx(n * 0.02, abs=1e-7)
    assert by_name["serve.retire"] == pytest.approx(n * 0.05, abs=1e-7)
    assert by_name["serve.snapshot"] == pytest.approx(n * 0.05, abs=1e-7)
    assert by_name.get("serve.device_wait", 0.0) < 1e-9
    # the rest of the slice's idle time is the harness between steps
    idle_s = ctx.summary.idle_share * window_s
    assert by_name[None] == pytest.approx(idle_s - n * 0.14, abs=1e-6)
    share = E.device_idle_engine_share(ctx)
    assert share == pytest.approx(100 * n * 0.14 / window_s, rel=1e-5)
    assert share <= 100 * ctx.summary.idle_share


def test_a_pair_that_disagrees_reads_none(monkeypatch):
    rec = record()
    # counted from the slice's start: the first admit+step, which opens
    # with the engine's step at 107
    assert E.align(trace_of(rec), rec, W0, W1) == pytest.approx(-107.0,
                                                                abs=1e-9)
    bad = trace_of(rec, nudge_s=5e-3)
    ctx = ctx_of(rec, bad, monkeypatch=monkeypatch)
    assert E.align(bad, rec, W0, W1) is None
    assert E.device_idle_engine_share(ctx) is None
    assert E.step_host_ms(ctx) is not None  # the ring alone still reads


def test_steps_that_pair_two_ways_read_none(monkeypatch):
    rec = record(uniform=True)
    tr = trace_of(rec)
    assert E.align(tr, rec, W0, W1) is None
    assert E.device_idle_engine_share(ctx_of(rec, tr,
                                             monkeypatch=monkeypatch)) is None


def test_a_ring_that_no_longer_reaches_the_window_reads_none(monkeypatch):
    rec = record(maxlen=8 * 15)  # the last 15 steps: from 105 on
    assert rec.dropped and not rec.covers(W0)
    ctx = ctx_of(rec, trace_of(rec), monkeypatch=monkeypatch)
    for m in METRICS:
        assert getattr(E, m)(ctx) is None, m


def test_nothing_is_read_off_the_tpu_or_without_spans(monkeypatch):
    rec = record()
    off_tpu = ctx_of(rec, None, peak=None, monkeypatch=monkeypatch)
    for m in METRICS:
        assert getattr(E, m)(off_tpu) is None, m
    no_spans = ctx_of(None, trace_of(rec), monkeypatch=monkeypatch)
    for m in METRICS:
        assert getattr(E, m)(no_spans) is None, m


def test_nothing_compiled_reads_zero_and_no_grid_reads_none(monkeypatch):
    rec = record()
    for st in rec.spans("serve.step"):
        st.attrs = {"compile_s": 0.0}
    for d in rec.spans("serve.decode"):
        d.attrs = {"rows": 10, "real_rows": 6}
    ctx = ctx_of(rec, None, monkeypatch=monkeypatch)
    assert E.window_compile_s(ctx) == 0.0
    assert E.paged_grid_live_share(ctx) is None
    assert E.row_fill_share(ctx) == pytest.approx(60.0)


def test_the_program_recorder_is_found():
    from repro.core.spans import default_recorder
    assert E.recorder() is default_recorder()
