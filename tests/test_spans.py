"""SpanRecorder: the spans and counters of each scheduler iteration.

The recorder alone (parent links, self time, the ring's bound, JAX compile
time as a union of nested events); the engine's span tree per step under a
ManualClock, its row and grid counters on a batch built by hand (and the
grid count against the paged kernel's own liveness test), the request
phase stamps in order; tokens stamped when their transfer ends (a clock
that ticks on every read tells the two apart); and the spans on the
profiler's clock in a CPU profile of one step. Each test that builds an
engine gets a fresh process-wide ring (the `rec` fixture)."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import spans as S
from repro.core.clock import Clock, ManualClock
from repro.core.spans import SpanRecorder
from repro.kernels.paged_decode.kernel import (block_computes, chunk_blocks,
                                              grid_programs, live_blocks)
from repro.models import model as Mo
from repro.models.env import Env
from repro.serve import (SERVE_PLAN, Request, ServingEngine,
                         make_serving_engine, run_to_completion)

CFG = get_smoke("paper-demo")
PARAMS = Mo.init_params(jax.random.PRNGKey(0), CFG, Env(None, SERVE_PLAN))
STEP_KIDS = ["serve.admit", "serve.decode", "serve.snapshot"]
DECODE_KIDS = ["serve.pack", "serve.dispatch", "serve.device_wait",
               "serve.retire"]


class TickClock(Clock):
    """Every read advances the clock by one tick."""

    def __init__(self, tick: float = 1.0):
        self.t, self.tick = 0.0, tick

    def now(self) -> float:
        self.t += self.tick
        return self.t

    def sleep(self, s: float) -> None:
        self.t += s


@pytest.fixture
def rec(monkeypatch):
    """A fresh process-wide ring for the engines the test builds."""
    fresh = SpanRecorder()
    monkeypatch.setattr(S, "_DEFAULT", fresh)
    return fresh


def _engine(clock=None, **kw):
    kw = {"num_slots": 2, "prompt_len": 32, "max_gen": 8,
          "prefill_chunk": 24, **kw}
    return ServingEngine(CFG, PARAMS, clock=clock or ManualClock(), **kw)


def _req(rid, plen, gen_len=3, arrival_t=0.0):
    rng = np.random.default_rng(rid)
    return Request(rid=rid, prompt=rng.integers(0, CFG.vocab_size, (plen,),
                                                dtype=np.int32),
                   gen_len=gen_len, arrival_t=arrival_t)


# -- the recorder alone -------------------------------------------------------
def test_parent_links_follow_nesting():
    rec, clock = SpanRecorder(), ManualClock()
    with rec.span("a", clock) as a:
        with rec.span("b", clock) as b:
            with rec.span("c", clock) as c:
                pass
        with rec.span("d", clock) as d:
            pass
    with rec.span("e", clock) as e:
        pass
    assert [s.name for s in rec.records] == list("abcde")
    assert a.parent is None and e.parent is None
    assert b.parent is a and d.parent is a and c.parent is b
    assert rec.children(a) == [b, d]


def test_self_time_under_a_ticking_clock():
    rec, clock = SpanRecorder(), TickClock()
    with rec.span("step", clock) as st:          # t=1
        with rec.span("wait", clock) as w:       # t=2
            clock.sleep(10)
        # wait closes at t=13
        with rec.span("retire", clock) as r:     # t=14
            pass
        # retire closes at t=15
    # step closes at t=16
    assert (st.start, st.end) == (1, 16)
    assert w.duration == 11 and r.duration == 1
    assert rec.children(st) == [w, r] and rec.children(w) == []
    self_s = st.duration - sum(c.duration for c in rec.children(st))
    assert self_s == 15 - 11 - 1
    st.attrs = {"rows": 4}
    assert rec.records[0].attrs == {"rows": 4}


def test_the_ring_is_bounded_and_says_how_far_it_reaches():
    rec, clock = SpanRecorder(maxlen=4), ManualClock()
    for _ in range(10):
        with rec.span("s", clock):
            clock.advance(1.0)
    assert len(rec.records) == 4 and rec.dropped == 6
    assert [s.start for s in rec.records] == [6.0, 7.0, 8.0, 9.0]
    assert rec.covers(6.0) and rec.covers(8.5)
    assert not rec.covers(5.0)
    assert SpanRecorder().covers(0.0)  # nothing dropped yet


def test_an_exception_closes_the_span():
    rec, clock = SpanRecorder(), ManualClock()
    with pytest.raises(ValueError):
        with rec.span("outer", clock):
            raise ValueError
    with rec.span("next", clock) as nxt:
        pass
    assert nxt.parent is None and rec.records[0].end is not None


def test_compile_time_is_the_union_of_nested_events(monkeypatch):
    monkeypatch.setattr(S, "_compile_spans", [])
    monkeypatch.setattr(S, "_compile_total", 0.0)
    ev = "/jax/core/compile/jaxpr_trace_duration"
    S._on_compile_span(ev, 101.0, 102.0)  # a nested trace closes first
    S._on_compile_span(ev, 101.5, 102.5)  # overlaps it
    S._on_compile_span(ev, 100.0, 103.0)  # the trace that holds both
    S._on_compile_span(ev, 105.0, 106.0)  # a later, separate compile
    S._on_compile_span("/jax/other", 107.0, 108.0)  # not a compile
    assert S.compile_seconds() == pytest.approx(4.0)


def test_every_engine_records_into_the_default_recorder(rec):
    a = make_serving_engine(CFG, PARAMS, num_slots=2, prompt_len=16,
                            max_gen=4)
    fleet = make_serving_engine(CFG, PARAMS, replicas=2, num_slots=2,
                                prompt_len=16, max_gen=4)
    assert S.default_recorder() is rec
    assert a.replica.spans is rec and fleet.spans is rec
    assert all(r.spans is rec for r in fleet.replicas)


# -- the engine ---------------------------------------------------------------
def _trees(rec):
    """Each serve.step with its children's names, and each decode's."""
    out = []
    for st in rec.spans("serve.step"):
        kids = rec.children(st)
        dec = [rec.children(k) for k in kids if k.name == "serve.decode"]
        out.append(([k.name for k in kids],
                    [[c.name for c in d] for d in dec]))
    return out


def test_one_span_tree_per_step(rec):
    eng = _engine()
    run_to_completion(eng, [_req(0, 20), _req(1, 12, arrival_t=0.1)],
                      dt=0.05)
    trees = _trees(rec)
    assert len(trees) == len(list(rec.spans("serve.step"))) > 3
    for kids, decodes in trees:
        assert kids in (STEP_KIDS, ["serve.admit", "serve.snapshot"])
        assert decodes == ([DECODE_KIDS] if "serve.decode" in kids else [])
    assert sum(1 for _ in rec.spans("serve.decode")) == eng.decode_steps
    assert all(s.end is not None for s in rec.records)
    assert all(s.attrs.keys() == {"compile_s"} and s.attrs["compile_s"] >= 0
               for s in rec.spans("serve.step"))


def test_replica_set_records_the_same_tree(rec):
    fleet = make_serving_engine(CFG, PARAMS, replicas=2, num_slots=2,
                                prompt_len=32, max_gen=8, prefill_chunk=24,
                                clock=ManualClock())
    run_to_completion(fleet, [_req(0, 20), _req(1, 12)], dt=0.05)
    first_kids, first_decodes = _trees(rec)[0]
    assert first_kids == ["serve.admit", "serve.decode", "serve.decode",
                          "serve.snapshot"]
    assert first_decodes == [DECODE_KIDS, DECODE_KIDS]


def test_row_and_grid_counters_of_a_batch_built_by_hand(rec):
    """2 slots, a 24-row lane budget, block 16, a 32 + 8 budget: the
    table is 3 blocks wide, so the paged kernel's chunk is the whole table
    (C = 3) and every row, padding included, runs one chunk of 3 slots.
    Step 1 runs request 0's 20-token prompt (depths 0..19: 16 rows in
    block 0, 4 reach block 1) in 2 + 24 rows; step 2 decodes it at depth
    20 beside request 1's 12-token lane."""
    eng = _engine()
    eng.submit([_req(0, 20, gen_len=4)])
    eng.step()
    eng.submit([_req(1, 12, gen_len=4)])
    eng.step()
    eng.step()
    d1, d2, d3 = [s.attrs for s in rec.spans("serve.decode")]
    assert d1 == {"rows": 26, "real_rows": 20, "grid_cells": 26 * 3,
                  "grid_live": 16 * 1 + 4 * 2}
    # one decode row at depth 20 (block 1), 12 lane rows at depths 0..11
    assert d2 == {"rows": 26, "real_rows": 13, "grid_cells": 26 * 3,
                  "grid_live": 2 + 12 * 1}
    # no lane left: two decode rows at depths 21 and 12
    assert d3 == {"rows": 2, "real_rows": 2, "grid_cells": 2 * 3,
                  "grid_live": 2 + 1}


@pytest.mark.parametrize("bs,width", [(16, 3), (8, 5), (1, 4), (16, 12),
                                      (16, 192), (8, 20)])
def test_the_grid_count_is_the_kernels_liveness_test(bs, width):
    """grid_programs counts, block by block, the slots of the paged
    kernel's chunk loop: a row runs ceil(live_blocks / C) chunks of C
    slots, and a slot holds a live block where block_computes holds and
    the table has the block (a masked row, -1, runs none; a row past the
    table's end reads all of it); the three rows past the six lengths are
    padding at depth 0, one chunk each, never live."""
    lengths = np.array([-1, 0, bs - 1, bs, 2 * bs + 3, width * bs + 7])
    C = chunk_blocks(bs, width)
    cells = live = 0
    for L, real in [(L, True) for L in lengths] + [(0, False)] * 3:
        for c in range(-(-int(live_blocks(np.asarray(L), bs, width)) // C)):
            for j in range(c * C, (c + 1) * C):
                cells += 1
                live += real and bool(block_computes(j, L, bs)) and j < width
    assert grid_programs(9, lengths, bs, width) == (cells, live)


def test_the_slot_backend_counts_rows_but_no_grid(rec):
    eng = _engine(kv="slot", prompt_len=16, prefill_chunk=0)
    run_to_completion(eng, [_req(0, 16)], dt=0.05)
    attrs = [s.attrs for s in rec.spans("serve.decode")]
    assert attrs and all(a == {"rows": 2, "real_rows": 1} for a in attrs)


def test_request_phases_are_stamped_in_order(rec):
    eng = _engine(clock=TickClock(1e-3), prefill_chunk=8)
    reqs = [_req(i, 20, arrival_t=0.0) for i in range(3)]
    run_to_completion(eng, reqs, dt=0.05)
    for r in reqs:
        assert r.t_admit <= r.t_lane_start <= r.t_first_token <= r.t_done
    # a 20-token prompt in an 8-row budget spans three steps; the lane
    # starts at the dispatch of the first
    dispatch = [s.start for s in rec.spans("serve.dispatch")]
    assert all(r.t_lane_start in dispatch for r in reqs)
    assert reqs[1].t_lane_start > reqs[0].t_lane_start


def test_the_classic_path_starts_its_lane_at_admission(rec):
    eng = _engine(kv="slot", prompt_len=16, prefill_chunk=0)
    r = _req(0, 16)
    run_to_completion(eng, [r], dt=0.05)
    assert r.t_lane_start == r.t_admit is not None


def test_the_classic_path_stamps_its_first_token_after_the_transfer(rec):
    """The batch-1 prefill's first token is read back inside serve.admit:
    under a ticking clock it is stamped there, after the step's start
    that admission carries, and so are its token record and an instant
    retirement."""
    eng = _engine(clock=TickClock(), kv="slot", prompt_len=16,
                  prefill_chunk=0)
    r = _req(0, 16, gen_len=1)
    eng.submit([r])
    eng.step()
    step, admit = next(rec.spans("serve.step")), next(rec.spans("serve.admit"))
    assert r.t_lane_start == r.t_admit == step.start
    assert admit.start < r.t_first_token == r.t_done < admit.end
    assert eng.metrics._tokens[-1] == (r.t_first_token, 1)


def test_tokens_are_stamped_when_their_transfer_ends(rec):
    """Under a clock that ticks on every read the step's start, the
    transfer's end and the stamps are all different times: the first
    token, the tokens' record and the retirement carry the transfer's
    end."""
    eng = _engine(clock=TickClock())
    r = _req(0, 20, gen_len=2)
    eng.submit([r])
    eng.step()
    wait = next(rec.spans("serve.device_wait"))
    step = next(rec.spans("serve.step"))
    assert r.t_first_token == wait.end > wait.start > step.start
    assert eng.metrics._tokens[-1] == (wait.end, 1)
    eng.step()
    wait2 = list(rec.spans("serve.device_wait"))[-1]
    assert r.t_done == wait2.end and eng.metrics._latency[-1][0] == wait2.end


def test_manual_clock_stamps_are_unchanged(rec):
    """Under a ManualClock nothing ticks inside a step: every stamp is the
    step's start, as before the transfer stamp."""
    eng = _engine()
    r = _req(0, 20, gen_len=2)
    eng.submit([r])
    eng.step()
    eng.clock.advance(0.25)
    eng.step()
    assert (r.t_admit, r.t_lane_start, r.t_first_token, r.t_done) == \
        (0.0, 0.0, 0.0, 0.25)


def test_spans_nest_on_the_profilers_clock(tmp_path, rec):
    """A CPU profile of one engine step holds the engine's spans as
    TraceAnnotations: serve.device_wait inside serve.decode inside
    serve.step."""
    eng = _engine()
    eng.submit([_req(0, 20)])
    eng.step()  # compile outside the profile
    eng.submit([_req(1, 12)])
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    ev = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    ev.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert {"serve.step", "serve.admit", "serve.decode", "serve.pack",
            "serve.dispatch", "serve.device_wait", "serve.retire",
            "serve.snapshot"} <= set(ev)
    (s0, s1), = ev["serve.step"]
    (d0, d1), = ev["serve.decode"]
    (w0, w1), = ev["serve.device_wait"]
    assert s0 <= d0 <= w0 <= w1 <= d1 <= s1
