"""Paper Table 3 analogue — per-component cost of Standard Attention vs HAD.

The paper synthesizes a CAM ASIC and reports area/power per attention
component (QK^T, top-N, softmax, AV) for one head, ctx 256, N=30. The CAM
energy numbers don't transfer to TPU (DESIGN.md §3/§7); what transfers is
the *work*: ops and bytes per component. This benchmark reports those for
the same configuration — analytically (exact op/byte counts of each
pipeline stage) and with a CPU wall-clock cross-check of the fused kernels
(interpret mode, correctness-grade timing only).

Paper's hardware result for context: 79% area / 87% power reduction.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hamming
from repro.kernels import ops as kops, ref as kref

CTX, N_TOP, DH, DV = 256, 30, 64, 64  # paper table 3: one head, ctx 256


def analytic_component_costs() -> dict:
    """Per-query-token op and byte counts for one head (ctx=256, N=30)."""
    t, d, dv, n = CTX, DH, DV, N_TOP
    w = hamming.packed_words(d)
    sa = {
        # float ops (MACs counted as 2 ops) and bytes moved per query
        "QK": {"ops": 2 * t * d, "bytes": t * d * 2 + d * 2 + t * 4},
        "TopN": {"ops": 0, "bytes": 0},              # SA keeps all T
        "Softmax": {"ops": 3 * t, "bytes": 2 * t * 4},
        "AV": {"ops": 2 * t * dv, "bytes": t * dv * 2 + dv * 4 + t * 4},
    }
    had = {
        # XOR+popcount+accumulate ~ 3 word-ops per 32 dims
        "QK": {"ops": 3 * t * w, "bytes": t * w * 4 + w * 4 + t * 4},
        # histogram threshold: one pass over T int scores + d+1 counters
        "TopN": {"ops": 2 * t, "bytes": t * 4 + (d + 1) * 4},
        # softmax over the ~N kept entries only
        "Softmax": {"ops": 3 * n, "bytes": 2 * n * 4},
        # AV accumulates only ~N rows of V
        "AV": {"ops": 2 * n * dv, "bytes": n * dv * 2 + dv * 4},
    }
    return {"SA": sa, "HAD": had}


def run(print_fn=print) -> list[str]:
    costs = analytic_component_costs()
    tot = {k: {"ops": sum(c["ops"] for c in v.values()),
               "bytes": sum(c["bytes"] for c in v.values())}
           for k, v in costs.items()}
    print_fn(f"table3: per-query component costs, ctx={CTX}, N={N_TOP}, "
             f"dh={DH} (paper: 79% area / 87% power reduction)")
    print_fn(f"{'component':>10} {'SA ops':>9} {'HAD ops':>9} "
             f"{'SA bytes':>9} {'HAD bytes':>10}")
    for comp in ("QK", "TopN", "Softmax", "AV"):
        sa, had = costs["SA"][comp], costs["HAD"][comp]
        print_fn(f"{comp:>10} {sa['ops']:>9} {had['ops']:>9} "
                 f"{sa['bytes']:>9} {had['bytes']:>10}")
    ops_red = 1 - tot["HAD"]["ops"] / tot["SA"]["ops"]
    byte_red = 1 - tot["HAD"]["bytes"] / tot["SA"]["bytes"]
    print_fn(f"{'total':>10} {tot['SA']['ops']:>9} {tot['HAD']['ops']:>9} "
             f"{tot['SA']['bytes']:>9} {tot['HAD']['bytes']:>10}")
    print_fn(f"reductions: ops {100 * ops_red:.1f}%  bytes "
             f"{100 * byte_red:.1f}%  (paper: area 79%, power 87%)")

    # wall-clock cross-check of the fused decode kernel vs a dense f32
    # reference (interpret mode on the CPU: correctness-grade only)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 1, DH)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 1, CTX, DH)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 1, CTX, DV)).astype(np.float32))
    qb, kb = hamming.pack_bits(q), hamming.pack_bits(k)
    lengths = jnp.asarray([CTX], jnp.int32)
    f = lambda: kops.decode_attention(qb, kb, v, d=DH, nsel=N_TOP,
                                      scale=DH ** -0.5, lengths=lengths,
                                      block_t=64)
    f()  # compile
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(f())
    us = (time.perf_counter() - t0) / 5 * 1e6
    return [f"table3_hardware,{us:.1f},ops_reduction={ops_red:.3f};"
            f"bytes_reduction={byte_red:.3f};paper_area=0.79;paper_power=0.87"]


if __name__ == "__main__":
    for line in run():
        print(line)
