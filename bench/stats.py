"""Percentiles and the latency sets the end-to-end metrics are taken over.

Percentiles interpolate linearly between closest ranks (numpy's default
`linear` method): p-th percentile of sorted x is x[(n-1)p/100], with
interpolation between the two neighbours.
"""
from __future__ import annotations

import math


def percentile(values, p: float) -> float | None:
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_samples(requests, window_start: float,
                 window_end: float) -> list[float]:
    """Seconds from due time to first token, for every request due inside
    the window (`due` counts from the window's start). A request with no
    first token by the window's end enters with its wait so far, so a
    stall cannot hide behind unserved requests."""
    out = []
    for r in requests:
        due = window_start + r.due
        if not r.in_window or due > window_end:
            continue
        first = r.token_times[0] if r.token_times else None
        out.append((window_end if first is None or first > window_end
                    else first) - due)
    return out


def itl_samples(requests, window_start: float, window_end: float) -> list[float]:
    """Gaps between consecutive output tokens of one request, where both
    tokens were committed inside the window."""
    out = []
    for r in requests:
        ts = [t for t in r.token_times if window_start <= t <= window_end]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(requests, window_start: float, window_end: float) -> int:
    return sum(1 for r in requests for t in r.token_times
               if window_start <= t <= window_end)
