"""paged_decode_roofline: least time of the window's paged-decode calls
(larger of algorithm bytes / HBM peak and algorithm ops / bf16 peak,
bench/work.py) over the summed device time of the kernel's events.

A decode step is one kernel call per layer; its slots' resident key counts
come from the tokens the step committed (a step's tokens share a commit
time). The least time per call is averaged over the window's steps and
multiplied by the number of kernel events."""
from __future__ import annotations

import collections

from bench import devtrace, ops, work


def read(run):
    tr = run.trace
    if tr is None:
        return None
    lo, hi = devtrace.window(tr)
    events = [o for o in tr.ops if ops.is_paged_decode(o.name)
              and lo <= o.start < hi]
    if not events:
        return None
    # token stamps are on the benchmark's clock, kernel events on the trace's
    steps = collections.defaultdict(list)
    for r in run.requests:
        plen = len(r.prompt)
        for j, t in enumerate(r.token_times):
            if j and run.window_start <= t <= run.window_end:
                steps[t].append(plen + j)
    if not steps:
        return None
    s = run.spec
    least = [work.least_time(*work.paged_decode_call(
        ctx, h=s["h"], hk=s["hk"], dh=s["dh"], nsel=run.nsel), run.peaks)[0]
        for ctx in steps.values()]
    per_call = sum(least) / len(least)
    return 100.0 * per_call * len(events) / sum(o.end - o.start for o in events)
