"""How the program's programs and kernels are named in a device trace.

A Pallas kernel's HLO instruction, and so its op in the trace, is named
after the function that calls `pallas_call` (`paged_decode_attention.9`,
read from the decode step compiled for a v5e), with the kernel function's
name as the fallback. Every jitted step is one program, told apart by the
kernel it runs: the decode step runs the paged-decode kernel, the
prefill-chunk step the prefill kernel.
"""
from __future__ import annotations

from bench import devtrace

PAGED_DECODE_KERNEL = ("paged_decode_attention", "_paged_decode_kernel")
PREFILL_KERNEL = ("prefill_attention", "_prefill_kernel")


def _named(name: str, names: tuple) -> bool:
    return name.split(".")[0] in names


def is_paged_decode(name: str) -> bool:
    return _named(name, PAGED_DECODE_KERNEL)


def is_prefill(name: str) -> bool:
    return _named(name, PREFILL_KERNEL)


# HLO opcodes that only move or re-lay-out data. XLA names a fusion after
# the opcodes it fuses, joined by "_" and ending in "fusion"
# (`constant_dynamic-slice_fusion.13`, `copy_bitcast_fusion.2`).
MOVEMENT_OPCODES = frozenset((
    "copy", "copy-start", "copy-done", "transpose", "bitcast", "reshape",
    "broadcast", "constant", "slice", "slice-start", "slice-done",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "pad"))


def is_copy(name: str) -> bool:
    """Data movement with no arithmetic: an op, or a fusion, whose name is
    made of data-movement opcodes alone (`copy.113`, `copy-done`,
    `slice-done.2`, `copy_bitcast_fusion.2`,
    `copy_dynamic-update-slice_fusion.2`, and the per-layer pool slices
    `constant_dynamic-slice_fusion.13`). A fusion named after arithmetic
    (`bitcast_add_fusion.3`, `dynamic-slice_convert_fusion.4`) or by number
    alone (`fusion.142`) is not counted."""
    words = name.split(".")[0].split("_")
    if words[-1] == "fusion":
        words = words[:-1]
    return bool(words) and all(w in MOVEMENT_OPCODES for w in words)


def step_executions(tr, lo, hi, predicate) -> list[tuple]:
    """(module event, its ops) of the program executions, starting in
    [lo, hi], that run an op matching `predicate`."""
    return [(m, ops) for m, ops in devtrace.executions(tr, lo, hi)
            if any(predicate(o.name) for o in ops)]
