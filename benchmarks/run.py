"""Benchmark aggregator — one harness per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (scaffold contract) after each
harness's human-readable output. ``--fast`` shrinks training budgets ~4x
for smoke usage; default budgets run the full proxies (~15-25 min on 1 CPU).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig1_runtime, fig3_topn, fig4_softmax,
                            fig5_quality, kernels_bench, roofline,
                            serve_bench, table1_glue, table2_imagenet,
                            table3_hardware)

    fast_kw = dict(steps_teacher=120, steps_per_stage=10, eval_batches=8)
    suites = [
        ("fig4_softmax", fig4_softmax.run, {}),
        ("table3_hardware", table3_hardware.run, {}),
        ("fig1_runtime", fig1_runtime.run, {}),
        ("kernels_bench", kernels_bench.run, {}),
        ("serve_bench", serve_bench.run,
         dict(slot_counts=(1, 2), n_req=2, stagger=2) if args.fast else {}),
        ("table1_glue", table1_glue.run, fast_kw if args.fast else {}),
        ("table2_imagenet", table2_imagenet.run, fast_kw if args.fast else {}),
        ("fig3_topn", fig3_topn.run,
         dict(steps_teacher=120, steps_per_stage=6, eval_batches=8)
         if args.fast else {}),
        ("fig5_quality", fig5_quality.run,
         dict(steps_teacher=120, steps_per_stage=8, eval_batches=6,
              ctxs=[64, 128]) if args.fast else {}),
        ("roofline", roofline.run, {}),
    ]
    if args.only:
        keep = set(args.only.split(","))
        suites = [s for s in suites if s[0] in keep]

    csv_lines: list[str] = []
    failed: list[str] = []
    for name, fn, kw in suites:
        print(f"\n===== {name} =====", flush=True)
        t0 = time.perf_counter()
        try:
            csv_lines.extend(fn(print_fn=print, **kw))
        except Exception:
            traceback.print_exc()
            csv_lines.append(f"{name},0.0,ERROR")
            failed.append(name)
        print(f"[{name}: {time.perf_counter() - t0:.0f}s]", flush=True)

    print("\n===== CSV (name,us_per_call,derived) =====")
    for line in csv_lines:
        print(line)
    if failed:
        print(f"suites that raised: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
