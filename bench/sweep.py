"""Find a traffic mix's knee: run an open-loop cell at several mean rates in
one process and print, per rate, what was offered and what was served.

    python3 bench/sweep.py --workload phi3m.chat --rates 2,3,4 --seconds 30 --seed 1

A rate is sustained where the requests due in the window are served about
as fast as they arrive and the first-token wait does not grow from the
window's first half to its second (no growing backlog). The knee found is
written into the mix's `rate_rps` by hand, as a number; the benchmark never
searches for a rate itself.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, place_compile_cache  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    place_compile_cache()
    from bench import harness, stats
    for rate in [float(x) for x in args.rates.split(",")]:
        t0 = time.perf_counter()
        out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               False, t_start=t0, scored=False,
                               mix_override={"rate_rps": rate})
        run = out["run"]
        lo, hi = run.window_start, run.window_end
        mid = lo + (hi - lo) / 2
        due = [r for r in run.requests if lo + r.due <= hi]
        first = [r for r in due if lo + r.due < mid]
        second = [r for r in due if lo + r.due >= mid]
        served = [r for r in due if r.token_times and r.token_times[0] <= hi]
        done = [r for r in due if len(r.tokens) >= r.max_new
                and r.token_times[-1] <= hi]

        def p90(rs):
            v = stats.percentile(stats.ttft_samples(rs, lo, hi), 90)
            return None if v is None else round(v * 1e3, 1)

        row = {"rate_rps": rate, "due": len(due),
               "first_token_by_end": len(served), "finished_by_end": len(done),
               "ttft_p90_ms_first_half": p90(first),
               "ttft_p90_ms_second_half": p90(second),
               "output_tok_s": stats.tokens_in_window(run.requests, lo, hi)
               / (hi - lo),
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
