"""Reduction of a profiler trace (`.xplane.pb`, read with
`jax.profiler.ProfileData`) to device busy time, idle gaps, time per
program execution and time per named op.

As a TPU v5e trace holds them (read by hand): each chip is a
`/device:TPU:<n>` plane. Its "XLA Modules" line has one event per program
execution (`jit__step(<fingerprint>)`), its "XLA Ops" line one event per
op, named by the op's whole HLO text (`%paged_decode_attention.9 = f32[...]
custom-call(...)`), with loop ops (`%while.5`, the scan over layers)
spanning the ops of their body. Host spans are the benchmark's own
`TraceAnnotation`s, read from the host plane by name. Times are seconds on
the trace's clock, which host and device planes share.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

# the benchmark's own host spans (run.py opens them around its calls)
BENCH_SPANS = ("window", "setup", "submit", "step_pipelined", "flush",
               "wait_arrival")


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str          # HLO instruction name, e.g. "paged_decode_attention.9"
    start: float
    end: float


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: list          # [Op] of every "XLA Ops" line, by start
    modules: list      # [Op] of every "XLA Modules" line: program executions
    spans: list
    devices: int


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """"%fusion.12 = bf16[...] fusion(...)" -> "fusion.12"."""
    return text.lstrip("%").split(" = ", 1)[0].strip()


def load(path: str) -> Trace:
    """Read an `.xplane.pb` (or a gzipped one, `.xplane.pb.gz`)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops, modules, spans, devices = [], [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices
            devices += 1
            for line in plane.lines:
                into = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if into is None:
                    continue
                for ev in line.events:
                    into.append(Op(dev, op_name(ev.name), ev.start_ns * 1e-9,
                                   (ev.start_ns + ev.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in BENCH_SPANS:
                        spans.append(Span(ev.name, ev.start_ns * 1e-9,
                                          (ev.start_ns + ev.duration_ns) * 1e-9))
    ops.sort(key=lambda o: (o.device, o.start, -o.end))
    modules.sort(key=lambda o: (o.device, o.start))
    return Trace(ops, modules, spans, devices)


def window(tr: Trace) -> tuple[float, float]:
    """Bounds of the measured window: the benchmark's `window` span."""
    ws = [s for s in tr.spans if s.name == "window"]
    if not ws:
        raise ValueError("trace has no 'window' span")
    return ws[0].start, ws[0].end


def _merged(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_intervals(tr: Trace, lo: float, hi: float, device: int = 0):
    return _merged((max(o.start, lo), min(o.end, hi)) for o in tr.ops
                   if o.device == device and o.end > lo and o.start < hi)


def busy_seconds(tr: Trace, lo: float, hi: float) -> float:
    """Union of device-op intervals inside [lo, hi], averaged over the
    trace's devices."""
    if tr.devices == 0:
        return 0.0
    total = sum(b - a for d in range(tr.devices)
                for a, b in busy_intervals(tr, lo, hi, d))
    return total / tr.devices


def idle_gaps(tr: Trace, lo: float, hi: float, device: int = 0):
    """[(label, start, seconds)] of every stretch in [lo, hi] with no op on
    the device, labelled by the innermost benchmark span (other than the
    window) that covers the gap's midpoint."""
    gaps, t = [], lo
    for a, b in busy_intervals(tr, lo, hi, device) + [(hi, hi)]:
        if a > t:
            gaps.append((t, a - t))
        t = max(t, b)
    inner = [s for s in tr.spans if s.name != "window"]
    out = []
    for start, dur in gaps:
        mid = start + dur / 2
        cover = [s for s in inner if s.start <= mid <= s.end]
        label = (min(cover, key=lambda s: s.end - s.start).name if cover
                 else "outside_spans")
        out.append((label, start, dur))
    return out


def self_seconds(ops) -> list[tuple[Op, float]]:
    """(op, self time): an op's duration less the time of the ops nested in
    it (a loop op spans the ops of its body)."""
    out, stack = [], []          # stack of [op, self]
    for o in ops:
        while stack and (stack[-1][0].device != o.device
                         or stack[-1][0].end <= o.start):
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(o.end, stack[-1][0].end) - o.start
        stack.append([o, o.end - o.start])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def op_seconds(tr: Trace, lo: float, hi: float) -> dict[str, float]:
    """Device self seconds per op name (summed over devices) of the ops
    that start inside [lo, hi]."""
    acc: dict[str, float] = collections.defaultdict(float)
    for o, s in self_seconds([o for o in tr.ops if lo <= o.start < hi]):
        acc[o.name] += s
    return dict(acc)


def executions(tr: Trace, lo: float, hi: float) -> list[tuple[Op, list]]:
    """(module event, the ops inside it) for the program executions that
    start inside [lo, hi]."""
    out = []
    for m in tr.modules:
        if lo <= m.start < hi:
            out.append((m, [o for o in tr.ops if o.device == m.device
                            and m.start <= o.start < m.end]))
    return out


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    ops = sorted(op_seconds(tr, lo, hi).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr, lo, hi), key=lambda g: -g[2])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[label, s] for label, _, s in gaps]}
