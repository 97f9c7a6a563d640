"""The work a step must do, counted from shapes alone, whatever implements
it. A later kernel cannot change these counts.

Paged HAD decode, one call (one layer, every active slot):
  bytes = sum over slots and kv heads of
            resident keys x dh/8                  (packed K sign bits)
          + min(N, resident) x dh x 2             (bf16 V rows of the kept keys)
        + slots x H x (dh/8 + 4 dh)               (query bits in, f32 output)
  ops   = sum over slots and query heads of
            resident x 2 dh                       (Hamming scores)
          + min(N, resident) x 2 dh               (P.V over the kept keys)
Model FLOPs of one served row (no padding rows, no recomputation):
  2 x (q, k, v, o projections + three FFN matrices) per layer
  + per layer the attention ops above at the row's context
  + 2 d V for the LM head of each sampled row.
"""
from __future__ import annotations


def paged_decode_call(contexts, *, h: int, hk: int, dh: int, nsel: int,
                      v_bytes: int = 2) -> tuple[float, float]:
    """(bytes, ops) of one paged decode call over slots whose resident key
    counts (current token included) are `contexts`."""
    keys = sum(contexts)
    kept = sum(min(nsel, c) for c in contexts)
    nbytes = (keys * hk * dh / 8 + kept * hk * dh * v_bytes
              + len(contexts) * h * (dh / 8 + 4 * dh))
    ops = (keys + kept) * h * 2 * dh
    return nbytes, ops


def least_time(nbytes: float, ops: float, peaks) -> tuple[float, str]:
    """Roofline time and the bound that sets it."""
    tb, to = nbytes / peaks.hbm_bw, ops / peaks.flops_bf16
    return (tb, "memory") if tb >= to else (to, "compute")


def matmul_flops_per_row(s: dict) -> float:
    d, h, hk, dh, f = s["d"], s["h"], s["hk"], s["dh"], s["f"]
    per_layer = d * h * dh + 2 * d * hk * dh + h * dh * d + 3 * d * f
    return 2.0 * s["layers"] * per_layer


def attention_ops_row(s: dict, context: int, nsel: int) -> float:
    return s["layers"] * s["h"] * 2.0 * s["dh"] * (context + min(nsel, context))


def head_flops(s: dict) -> float:
    return 2.0 * s["d"] * s["vocab"]


def prefill_flops(s: dict, prompt_len: int, nsel: int) -> float:
    """Every prompt row once, and the head of its last row."""
    p, n = prompt_len, nsel
    keys = p * (p + 1) / 2
    kept = keys if p <= n else n * (n + 1) / 2 + (p - n) * n
    att = s["layers"] * s["h"] * 2.0 * s["dh"] * (keys + kept)
    return prompt_len * matmul_flops_per_row(s) + att + head_flops(s)


def decode_flops(s: dict, context: int, nsel: int) -> float:
    """One decode row at `context` resident keys (its own included)."""
    return (matmul_flops_per_row(s) + attention_ops_row(s, context, nsel)
            + head_flops(s))
