"""SpanRecorder — where one scheduler iteration's time goes.

A span is one named stretch of host time with a parent, stamped on the
engine's injected Clock (core/clock.py): under a ManualClock the stamps
replay deterministically, under the RealClock they are the same
monotonic clock a benchmark's client reads. A span may carry a dict of
attributes (counts: rows packed, grid cells, compile seconds).

Records live in memory in a bounded ring (the newest RING_SPANS), so an
engine can record forever; `covers(t)` says whether the ring still
reaches back to time t. Every span also enters
`jax.profiler.TraceAnnotation(name)`, so a profile of the program shows
the engine's spans nested on the profiler's clock beside the device ops
(inert when no profiler runs).

Every engine records into one process-wide recorder, `default_recorder()`
(as `logging` has its root logger), so a reader that outlives the engine
(a benchmark's, after the engine is dropped) still finds its spans there.
A recorder belongs to one thread: the open-span stack that sets each
span's parent is per recorder.

`compile_seconds()` is the process's cumulative time inside JAX's
tracing, lowering and compiling (the `/jax/core/compile/*` events, their
union: a nested jit's trace lies inside its caller's), read by the
engine around each step.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Deque, Dict, Iterator, List, Optional

import jax

from repro.core.clock import Clock

# about 2000 scheduler iterations of 8 spans: a benchmark's lead-in and
# window at a few hundred ms per step, or the window alone at tens of ms
RING_SPANS = 1 << 14


class Span:
    """One record: name, start, end (None while open), parent span (None
    at the root), attributes (None, or a dict of counts)."""
    __slots__ = ("name", "start", "end", "parent", "attrs")

    def __init__(self, name: str, start: float, parent: Optional["Span"]):
        self.name = name
        self.start = start
        self.end: Optional[float] = None
        self.parent = parent
        self.attrs: Optional[Dict[str, float]] = None

    @property
    def duration(self) -> float:
        return self.end - self.start


class _Scope:
    """The context manager `SpanRecorder.span` returns."""
    __slots__ = ("_rec", "_name", "_clock", "_span", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str, clock: Clock):
        self._rec, self._name, self._clock = rec, name, clock

    def __enter__(self) -> Span:
        rec = self._rec
        stack = rec._open
        sp = Span(self._name, self._clock.now(), stack[-1] if stack else None)
        if len(rec.records) == rec.records.maxlen:
            rec.dropped += 1
        rec.records.append(sp)
        stack.append(sp)
        self._span = sp
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        return sp

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        self._span.end = self._clock.now()
        self._rec._open.pop()


class SpanRecorder:
    """Spans in a bounded ring, in the order they opened."""

    def __init__(self, maxlen: int = RING_SPANS):
        self.records: Deque[Span] = deque(maxlen=maxlen)
        self.dropped = 0  # records the ring has pushed out
        self._open: List[Span] = []

    def span(self, name: str, clock: Clock) -> _Scope:
        """`with rec.span(name, clock) as sp:` records one span, a child
        of the innermost span open on this recorder; set `sp.attrs` to
        attach counts."""
        return _Scope(self, name, clock)

    def covers(self, t: float) -> bool:
        """Does the ring hold every span that opened at or after t?"""
        return self.dropped == 0 or (bool(self.records)
                                     and self.records[0].start <= t)

    def spans(self, name: Optional[str] = None) -> Iterator[Span]:
        return (s for s in self.records if name is None or s.name == name)

    def children(self, parent: Span) -> List[Span]:
        """Direct children of `parent` still in the ring."""
        return [s for s in self.records if s.parent is parent]


_DEFAULT = SpanRecorder()


def default_recorder() -> SpanRecorder:
    """The process-wide recorder every engine records into."""
    return _DEFAULT


# -- JAX compile time ---------------------------------------------------------
_COMPILE_EVENTS = "/jax/core/compile/"
_compile_lock = threading.Lock()
_compile_total = 0.0
# merged [start, end] stretches, end-ordered: events arrive as they close,
# so an outer trace arrives after the nested traces it holds
_compile_spans: List[List[float]] = []


def _on_compile_span(event: str, start: float, end: float, **_kw) -> None:
    global _compile_total
    if not event.startswith(_COMPILE_EVENTS) or end <= start:
        return
    with _compile_lock:
        added = end - start
        lo, hi = start, end
        while _compile_spans and _compile_spans[-1][1] > start:
            a, b = _compile_spans.pop()
            added -= max(0.0, min(b, end) - max(a, start))
            lo, hi = min(lo, a), max(hi, b)
        _compile_spans.append([lo, hi])
        del _compile_spans[:-64]
        _compile_total += added


jax.monitoring.register_event_time_span_listener(_on_compile_span)


def compile_seconds() -> float:
    """Seconds this process has spent in JAX tracing, lowering and
    compiling so far (cumulative; take differences)."""
    return _compile_total
