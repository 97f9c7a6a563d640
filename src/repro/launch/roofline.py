"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch, shape, mesh), in seconds (EXPERIMENTS.md §Roofline):

    compute    = HLO_FLOPs / (chips * peak bf16 FLOP/s)
    memory     = HLO_bytes / (chips * HBM bandwidth)
    collective = collective_bytes / (chips * ICI bandwidth per link)

Implementation note (validated against an analytic matmul): after SPMD
partitioning, compiled.cost_analysis() / memory_analysis() / as_text() all
describe the PER-DEVICE program, so the chips division is already applied —
the terms below consume per-device numbers directly and report global FLOPs
as flops * chips. Collective bytes are parsed from the per-device HLO text —
summed operand sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute.

Chip peaks live in one table keyed by JAX's `device_kind`; a kind that is
not in the table raises instead of falling back to another chip's numbers.
The analytic dry-run models `MODELED_KIND` and records it in every cell.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float        # FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    ici_bw: float            # bytes/s per link (one link budgeted)


# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect per chip over 4 links (50 GB/s per link).
CHIP_PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}

MODELED_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Published peaks of one chip of this `jax.Device.device_kind`."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}") \
            from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# shapes like bf16[2048,8192]{1,0} or f32[] or (bf16[..], f32[..]) tuples
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-type operand bytes summed over the module.

    Counts each collective op's *operand* sizes (the data that crosses the
    interconnect; for all-gather the per-chip contribution). Fusion bodies
    don't contain collectives, so a line scan is exact for SPMD modules.
    """
    out: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z\-]+)\(", stripped)
        if not m:
            continue
        op = m.group(1)
        if op.endswith("-start"):
            op = op[:-6]
        if op not in _COLLECTIVES:
            continue
        # operands appear inside the call parens; result shape before '='.
        call = stripped[m.end():]
        shapes = _SHAPE_RE.findall(call)
        if not shapes:  # fall back to the result shape
            shapes = _SHAPE_RE.findall(stripped.split("=")[1])
        out[op] += sum(_shape_bytes(d, s) for d, s in shapes)
    return out


@dataclasses.dataclass
class RooflineTerms:
    """All byte/flop fields are PER-DEVICE (see module docstring)."""

    flops: float
    bytes_hbm: float
    bytes_collective: float
    chips: int

    @property
    def global_flops(self) -> float:
        return self.flops * self.chips

    @property
    def t_compute(self) -> float:
        return self.flops / chip_peaks(MODELED_KIND).flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_hbm / chip_peaks(MODELED_KIND).hbm_bw

    @property
    def t_collective(self) -> float:
        return self.bytes_collective / chip_peaks(MODELED_KIND).ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "global_flops": self.global_flops,
            "bytes_hbm": self.bytes_hbm,
            "bytes_collective": self.bytes_collective, "chips": self.chips,
            "device_kind": MODELED_KIND,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
        }


def terms_from_compiled(compiled, hlo_text: str, chips: int) -> RooflineTerms:
    """Loop-aware terms via repro.launch.hlo_cost (XLA's cost_analysis
    counts while bodies once — see tests/test_hlo_cost.py)."""
    from repro.launch import hlo_cost as HC
    c = HC.module_cost(hlo_text)
    return RooflineTerms(c.flops, c.bytes, c.collective_bytes, chips)


def xla_reference_cost(compiled) -> dict:
    """XLA's own (loop-undercounting) numbers, kept for cross-reference."""
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def model_flops(cfg, shape, *, distill: bool = False) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) useful-work reference.

    Training processes D = batch*seq tokens with fwd+bwd (6ND). Distill
    adds the teacher forward (2ND). Decode/prefill are forward-only (2ND).
    """
    from repro.models.model import active_param_count
    n = active_param_count(cfg)
    d_tokens = shape.global_batch * (1 if shape.kind == "decode"
                                     else shape.seq_len)
    if shape.kind == "train":
        per_tok = 8 * n if distill else 6 * n   # 6 student + 2 teacher fwd
    else:
        per_tok = 2 * n
    return float(per_tok) * d_tokens
