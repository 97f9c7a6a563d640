"""Model FLOP utilisation of the window: the FLOPs the served rows need
(bench/work.py), over window seconds times the chip's bf16 peak.

Rows counted: every decode row whose token was committed in the window,
and every prompt of a request whose first token was committed in the
window (its whole prefill, counted once)."""
from __future__ import annotations

from bench import work


def step_mfu(run) -> float | None:
    s, n = run.spec, run.nsel
    lo, hi = run.window_start, run.window_end
    flops = 0.0
    for r in run.requests:
        plen = len(r.prompt)
        for j, t in enumerate(r.token_times):
            if not lo <= t <= hi:
                continue
            flops += (work.prefill_flops(s, plen, n) if j == 0
                      else work.decode_flops(s, plen + j, n))
    if not flops:
        return None
    return 100.0 * flops / (run.window_s * run.peaks.flops_bf16)
