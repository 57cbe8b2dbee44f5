"""Per-layer metrics read from the engine's own records: the spans and
counters of each scheduler iteration (repro.core.spans, one process-wide
ring the engine records into) and the phase stamps on each Request
(t_admit, t_lane_start, t_first_token).

Every reader returns None where it has nothing to read: off the TPU (the
fused step then runs the XLA gather path, not the chip's kernels), in a
program that records no spans (an older commit), when the ring no longer
reaches back to the window's start, or with no sample in the window.

The device trace keeps only the harness's own host spans, so the engine's
spans are put on the trace's clock by pairing: each traced `admit+step`
span wraps exactly one `serve.step`. The offset is the median of the
paired start differences; every pair must agree with it, and in duration,
within ALIGN_TOL_S, and only one pairing near the slice's start may do so.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench.lib import stats
from bench.lib.harness import TRACE_SLICE_S

ALIGN_TOL_S = 1e-3
# pairings tried from the first engine step that opened at or after the
# slice's start: the profiler starts after a clock read at or past it, so
# the first traced step is that one, or the next when that one opened
# between the read and the profiler's start
ALIGN_SHIFTS = (0, 1)
STEP, DECODE, WAIT = "serve.step", "serve.decode", "serve.device_wait"


def recorder():
    """The program's process-wide span ring; None in a program without
    one."""
    try:
        from repro.core.spans import default_recorder
    except ImportError:
        return None
    return default_recorder()


def _ring(ctx):
    """The ring, where this is a chip run and it reaches back to w0."""
    if ctx.peak is None:
        return None
    rec = recorder()
    if rec is None or not rec.covers(ctx.window.w0):
        return None
    return rec


@dataclass
class Step:
    """One closed serve.step span with the fused steps it held."""
    span: object
    decodes: List[object] = field(default_factory=list)
    waits: List[object] = field(default_factory=list)

    @property
    def wait_s(self) -> float:
        return sum(w.duration for w in self.waits)


def window_steps(rec, w0: float, w1: float) -> List[Step]:
    """The serve.step spans that opened in [w0, w1) and closed."""
    steps: Dict[int, Step] = {}
    for s in rec.records:
        if s.name == STEP:
            if w0 <= s.start < w1 and s.end is not None:
                steps[id(s)] = Step(s)
        elif s.name == DECODE and id(s.parent) in steps:
            steps[id(s.parent)].decodes.append(s)
        elif s.name == WAIT and s.parent is not None \
                and id(s.parent.parent) in steps:
            steps[id(s.parent.parent)].waits.append(s)
    return list(steps.values())


def _fused(ctx) -> Optional[List[Step]]:
    rec = _ring(ctx)
    if rec is None:
        return None
    w = ctx.window
    fused = [st for st in window_steps(rec, w.w0, w.w1) if st.decodes]
    return fused or None


# -- scheduler and fused step: request stamps ---------------------------------
def lane_wait_p95_ms(ctx) -> Optional[float]:
    """p95 of t_lane_start - t_admit over the requests admitted in the
    window; one whose lane had not started by the close counts at its
    elapsed time."""
    if _ring(ctx) is None:
        return None
    w = ctx.window
    out = []
    for r in w.recs:
        adm = r.req.t_admit
        if adm is None or not w.w0 <= adm < w.w1:
            continue
        ls = r.req.t_lane_start
        out.append((ls if ls is not None and ls <= w.w1 else w.w1) - adm)
    q = stats.quantile(out, .95)
    return None if q is None else q * 1e3


def prefill_p95_ms(ctx) -> Optional[float]:
    """p95 of t_first_token - t_lane_start over the requests whose lane
    started in the window; one with no first token by the close counts at
    its elapsed time."""
    if _ring(ctx) is None:
        return None
    w = ctx.window
    out = []
    for r in w.recs:
        ls = r.req.t_lane_start
        if ls is None or not w.w0 <= ls < w.w1:
            continue
        ft = r.req.t_first_token
        out.append((ft if ft is not None and ft <= w.w1 else w.w1) - ls)
    q = stats.quantile(out, .95)
    return None if q is None else q * 1e3


# -- the step's spans and counters --------------------------------------------
def step_host_ms(ctx) -> Optional[float]:
    """Mean host time of the window's steps that ran a fused step: the
    serve.step span less its serve.device_wait."""
    fused = _fused(ctx)
    if fused is None:
        return None
    return 1e3 * statistics.fmean(st.span.duration - st.wait_s
                                  for st in fused)


def step_device_wait_ms(ctx) -> Optional[float]:
    """Mean serve.device_wait of the same steps."""
    fused = _fused(ctx)
    if fused is None:
        return None
    return 1e3 * statistics.fmean(st.wait_s for st in fused)


def _share(ctx, part: str, whole: str) -> Optional[float]:
    fused = _fused(ctx)
    if fused is None:
        return None
    attrs = [d.attrs for st in fused for d in st.decodes
             if d.attrs and whole in d.attrs]
    total = sum(a[whole] for a in attrs)
    return 100.0 * sum(a[part] for a in attrs) / total if total else None


def row_fill_share(ctx) -> Optional[float]:
    """Real rows / rows run, over the window's fused steps."""
    return _share(ctx, "real_rows", "rows")


def paged_grid_live_share(ctx) -> Optional[float]:
    """Paged-kernel grid programs over a live table block / all grid
    programs, over the window's fused steps."""
    return _share(ctx, "grid_live", "grid_cells")


def window_compile_s(ctx) -> Optional[float]:
    """Seconds JAX spent tracing and compiling inside the window's
    steps; 0.0 when nothing compiled."""
    rec = _ring(ctx)
    if rec is None:
        return None
    steps = window_steps(rec, ctx.window.w0, ctx.window.w1)
    if not steps:
        return None
    return float(sum((st.span.attrs or {}).get("compile_s", 0.0)
                     for st in steps))


# -- device: idle time named by the engine span over it -----------------------
def _base_ns(trace) -> int:
    """The slice's start on the trace's clock: its first host span."""
    return min(s for _, s, _ in trace.host_spans)


def align(trace, rec, w0: float, w1: float) -> Optional[float]:
    """Seconds to add to an engine clock stamp to put it on the trace's
    clock, counted from the slice's start (so that sub-microsecond
    differences survive in a float), or None when the traced admit+step
    spans do not pair with the ring's serve.step spans."""
    base = _base_ns(trace)
    traced = sorted(((s - base) * 1e-9, d * 1e-9)
                    for n, s, d in trace.host_spans if n == "admit+step")
    steps = sorted((s for s in rec.spans(STEP) if s.end is not None),
                   key=lambda s: s.start)
    if not traced:
        return None
    t_slice = w0 + max(w1 - w0 - TRACE_SLICE_S, 0.0) / 2
    i0 = next((i for i, s in enumerate(steps) if s.start >= t_slice), None)
    if i0 is None:
        return None
    found = []
    for shift in ALIGN_SHIFTS:
        i = i0 + shift
        if i < 0 or i + len(traced) > len(steps):
            continue
        pairs = list(zip(traced, steps[i:i + len(traced)]))
        offs = [s - sp.start for (s, _), sp in pairs]
        off = statistics.median(offs)
        if all(abs(o - off) <= ALIGN_TOL_S for o in offs) and all(
                abs(d - sp.duration) <= ALIGN_TOL_S for (_, d), sp in pairs):
            found.append(off)
    return found[0] if len(found) == 1 else None


def _idle(trace) -> Tuple[float, List[Tuple[float, float]]]:
    """The slice's length and its idle stretches, in seconds from its
    start, as bench/lib/trace.py reduces them."""
    w0 = _base_ns(trace)
    w1 = max(s + d for _, s, d in trace.host_spans)
    busy = sorted((max(s, w0), min(s + d, w1))
                  for _, s, d in trace.device_ops)
    gaps, t = [], w0
    for a, b in busy:
        if b <= a:
            continue
        if a > t:
            gaps.append(((t - w0) * 1e-9, (a - w0) * 1e-9))
        t = max(t, b)
    if t < w1:
        gaps.append(((t - w0) * 1e-9, (w1 - w0) * 1e-9))
    return (w1 - w0) * 1e-9, gaps


def _segments(rec, off: float, lo: float, hi: float):
    """Disjoint stretches of the trace clock in [lo, hi], in order, each
    named by the innermost engine span open over it (spans nest: one
    thread records them)."""
    ev = []
    for s in rec.records:
        if s.end is None or s.end + off < lo or s.start + off > hi:
            continue
        d = _depth(s)
        ev.append((s.start + off, 1, d, s.name))
        ev.append((s.end + off, 0, -d, s.name))
    ev.sort()
    segs, stack, t = [], [], None
    for x, opening, _, name in ev:
        if stack and x > t:
            segs.append((t, x, stack[-1]))
        if opening:
            stack.append(name)
        else:
            stack.pop()
        t = x
    return segs


def _depth(s) -> int:
    d = 0
    while s.parent is not None:
        s, d = s.parent, d + 1
    return d


Split = Dict[Optional[str], float]


def idle_by_span(ctx) -> Optional[Tuple[float, Split]]:
    """The traced slice's seconds, and its idle seconds by the innermost
    engine span open over them (None: no engine span)."""
    rec = _ring(ctx)
    tr = ctx.window.trace
    if rec is None or ctx.summary is None or tr is None \
            or not tr.host_spans or not tr.device_ops:
        return None
    off = align(tr, rec, ctx.window.w0, ctx.window.w1)
    if off is None:
        return None
    window_s, gaps = _idle(tr)
    segs = _segments(rec, off, gaps[0][0], gaps[-1][1]) if gaps else []
    by_name: Split = {None: sum(b - a for a, b in gaps)}
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            x, y, name = segs[k]
            ov = min(y, b) - max(x, a)
            if ov > 0:
                by_name[name] = by_name.get(name, 0.0) + ov
                by_name[None] -= ov
            k += 1
    return window_s, by_name


def device_idle_engine_share(ctx) -> Optional[float]:
    """Device-idle time of the traced slice during which the host was
    inside an engine span other than serve.device_wait, over the slice."""
    got = idle_by_span(ctx)
    if got is None:
        return None
    window_s, by_name = got
    return 100.0 * sum(v for k, v in by_name.items()
                       if k is not None and k != WAIT) / window_s
