"""Run one benchmark cell and print its result as the last line of stdout.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU, fewer chips
than the cell needs, or a device kind without published peaks. Not part of
a benchmark run: `--control 1` also scores the fp8 control; `--fault
<name>` plants one of `bench/faults.py`'s faults in the timed path;
`--keep-trace DIR` copies the traced window's profile to DIR.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# libtpu would otherwise write its logs under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def place_compile_cache() -> None:
    """JAX's persistent cache: $JAX_COMPILATION_CACHE_DIR when set, else the
    fixed directory <checkout>/.jax_cache."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    place_compile_cache()
    from bench import harness
    if args.fault:
        from bench import faults
        sys.path.insert(0, str(ROOT / "src"))
        faults.FAULTS[args.fault](setattr)
    try:
        line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START,
                                control=bool(args.control),
                                keep_trace=args.keep_trace)
    except (harness.NoDevice, KeyError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
