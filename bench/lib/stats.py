"""Statistics over a run's client-side records.

Every tail is taken over all requests (or all gaps) of the window; a
request that has no first token when the window closes counts at its
elapsed time, so a stalled request raises the tail instead of leaving it.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-quantile (0..1) by linear interpolation between order
    statistics; None for no samples."""
    vs = sorted(values)
    if not vs:
        return None
    pos = q * (len(vs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (pos - lo)


def ttft_samples(requests, w0: float, w1: float):
    """Seconds from due time to first token on the host, for every request
    due in [w0, w1); a request with no first token by w1 counts at
    w1 - due."""
    out = []
    for r in requests:
        if not w0 <= r.due < w1:
            continue
        first = r.token_times[0] if r.token_times else None
        out.append((first if first is not None and first <= w1 else w1)
                   - r.due)
    return out


def gap_samples(requests, w0: float, w1: float):
    """Seconds between successive tokens of one request, for every gap
    whose later token reached the host in [w0, w1]."""
    out = []
    for r in requests:
        ts = r.token_times
        for a, b in zip(ts, ts[1:]):
            if w0 <= b <= w1:
                out.append(b - a)
    return out


def tokens_in(requests, w0: float, w1: float) -> int:
    """Output tokens that reached the host in [w0, w1]."""
    return sum(1 for r in requests for t in r.token_times if w0 <= t <= w1)


def queue_wait_samples(requests, w0: float, w1: float):
    """Seconds from due time to admission, for every request due in
    [w0, w1); one not admitted by w1 counts at w1 - due."""
    out = []
    for r in requests:
        if not w0 <= r.due < w1:
            continue
        adm = r.t_admit
        out.append((adm if adm is not None and adm <= w1 else w1) - r.due)
    return out
