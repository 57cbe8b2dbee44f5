"""The device trace of a run's traced slice, and its reduction to the
numbers the per-layer metrics read.

A trace is reduced from plain event lists, so the same code runs on a
profile the run just wrote and on the small recorded trace the tests keep:
  device ops   (name, start_ns, duration_ns) of every op on the chip
  host spans   (name, start_ns, duration_ns) of the benchmark's own spans
Busy time is the union of the op intervals inside the slice; the slice is
the stretch the host spans cover. Each idle gap is named by the host span
it fell in.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

# the harness's host spans, one of which is open at every moment of the
# traced slice
SPANS = ("admit+step", "harness bookkeeping", "idle wait for arrival")

Event = Tuple[str, int, int]


@dataclass
class Trace:
    device_ops: List[Event] = field(default_factory=list)
    host_spans: List[Event] = field(default_factory=list)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls([tuple(e) for e in d["device_ops"]],
                   [tuple(e) for e in d["host_spans"]])


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def _leaves(events) -> List[Event]:
    """Drop the ops that hold others on the same line (a while loop around
    its body's ops), so each device second is counted once, at the op that
    did the work."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, s, d) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= s + d:
            out.append((n, s, d))
    return out


def load_profile(log_dir: str) -> Trace:
    """Events of the one profile written under log_dir: the leaf ops on
    the 'XLA Ops' lines of the TPU planes, and the harness's host
    spans."""
    import jax  # the profiler's reader ships with JAX
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profile under {log_dir}, found "
                           f"{len(paths)}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    tr = Trace()
    for plane in pd.planes:
        device = _is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name == "XLA Ops":
                tr.device_ops += _leaves((e.name, e.start_ns, e.duration_ns)
                                         for e in line.events)
            elif not device:
                tr.host_spans += [(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events if e.name in SPANS]
    return tr


def _merge(intervals: Sequence[Tuple[float, float]]):
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]  # op name -> summed device seconds
    idle_gaps: List[Tuple[str, float]]  # longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, name: str) -> float:
        """Device seconds of the ops whose name holds `name`."""
        return sum(s for n, s in self.op_s.items() if name in n)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]


def reduce(tr: Trace) -> Optional[Summary]:
    """None when the trace holds no host span or no device op (nothing to
    read)."""
    if not tr.host_spans or not tr.device_ops:
        return None
    w0 = min(s for _, s, _ in tr.host_spans)
    w1 = max(s + d for _, s, d in tr.host_spans)
    clipped = [(n, max(s, w0), min(s + d, w1)) for n, s, d in tr.device_ops]
    clipped = [(n, a, b) for n, a, b in clipped if b > a]
    busy = _merge([(a, b) for _, a, b in clipped])
    busy_ns = sum(b - a for a, b in busy)
    per_op: Dict[str, float] = {}
    for n, a, b in clipped:
        per_op[n] = per_op.get(n, 0.0) + (b - a) * 1e-9
    spans = sorted((s, s + d, n) for n, s, d in tr.host_spans)
    gaps = []
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        name = next((n for s, e, n in spans if s <= mid < e), "no span")
        gaps.append((name, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9,
                   op_s=per_op, idle_gaps=gaps)

