"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, traffic mix or metric is
found by name: `bench/configs/<config>.json` (its `bench.arch` names
`bench/reference/<arch>.py` and `bench/adapters/<arch>.py`),
`bench/traffic/<mix>.json`, and `bench/metrics/<metric>.py`, which defines
`read(run) -> float | None`. Adding any of them needs no edit here.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

import numpy as np

from bench import traffic



class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file by path (names such as `step_mfu.decode.py` are not
    importable as modules)."""
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    return cell, confs[cell["config"]]


def metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def topn(had: dict, max_len: int) -> int:
    """The paper's rule (§4.3): N is a fixed share of the context, clamped."""
    return int(max(had["n_min"], min(had["n_max"],
                                     round(had["topn_frac"] * max_len))))


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""
    cell: dict
    conf: dict
    mix: dict
    spec: dict                 # static sizes (reference.spec_from_config)
    nsel: int
    peaks: object
    seconds: float
    window_start: float
    window_end: float
    setup_s: float
    requests: list
    counters: dict             # program counters, window deltas
    sched: dict                # engine.overlap_stats() window deltas
    trace: object = None       # devtrace.Trace of the window (--trace 1)

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start


def device_info(require_tpu: bool, chips: int):
    import jax
    from bench import peaks as P
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoDevice(f"no TPU: JAX found {devs[0].platform} devices")
        if len(devs) < chips:
            raise NoDevice(f"cell needs {chips} chips, JAX found {len(devs)}")
        peaks = P.chip_peaks(kind)
    else:
        peaks = P.CHIP_PEAKS.get(kind, P.CHIP_PEAKS["TPU v5 lite"])
    return devs, peaks


def _enter(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


class TokenClock:
    """Stamps committed tokens on the benchmark clock. Tokens that one call
    into the engine commits share one stamp (the time the first of them
    arrived), so a stamp identifies the device step that made them. Also
    keeps each call's start and length, and the garbage collector's pauses
    while `gc_log` is on, for the run's log."""

    def __init__(self, clock):
        self.clock = clock
        self.by_id: dict[int, object] = {}
        self._stamp = None
        self.calls: list[tuple[float, float]] = []
        self.gc_pauses: list[tuple[int, float, float]] = []
        self._gc_t = None

    def new_step(self):
        self._stamp = None

    def gc_log(self, phase, info):
        if phase == "start":
            self._gc_t = self.clock()
        elif self._gc_t is not None:
            self.gc_pauses.append((info["generation"], self._gc_t,
                                   self.clock() - self._gc_t))
            self._gc_t = None

    def host_summary(self, w0: float, w1: float) -> str:
        """The window's longest engine calls and garbage-collector pauses."""
        calls = sorted(((d, t) for t, d in self.calls if w0 <= t <= w1),
                       reverse=True)
        gens = [sum(1 for g, t, _ in self.gc_pauses if g == n and w0 <= t <= w1)
                for n in range(3)]
        longest_gc = max((d for _, t, d in self.gc_pauses if w0 <= t <= w1),
                         default=0.0)
        top = ", ".join(f"{d:.3f} s at {t - w0:.1f} s" for d, t in calls[:3])
        return (f"engine calls {len(calls)}, longest {top}; gc collections "
                f"by generation {gens}, longest pause {longest_gc:.3f} s")

    def sink(self, rid, tok):
        if self._stamp is None:
            self._stamp = self.clock()
        r = self.by_id.get(rid)
        if r is not None:
            r.token_times.append(self._stamp)
            r.tokens.append(int(tok))


def step(eng, tc, name="step_pipelined"):
    tc.new_step()
    t = tc.clock()
    with _enter(name):
        out = eng.step_pipelined() if name == "step_pipelined" else eng.flush()
    tc.calls.append((t, tc.clock() - t))
    return out


def drive(eng, reqs, mix, seconds, tc):
    """The measured window. Returns (start, end) on the benchmark clock."""
    clock = tc.clock
    t0 = clock()
    end = t0 + seconds
    if mix["loop"] == "closed":
        while clock() < end:
            step(eng, tc)
        return t0, clock()
    pending = sorted((r for r in reqs), key=lambda r: r.due)
    i = 0
    while True:
        now = clock()
        if now >= end:
            break
        with _enter("submit"):
            while i < len(pending) and t0 + pending[i].due <= now:
                submit(eng, pending[i], clock, tc.by_id)
                i += 1
        busy = bool(eng.queue) or any(s.request is not None for s in eng.slots)
        if busy:
            step(eng, tc)
        else:
            step(eng, tc, "flush")
            nxt = t0 + pending[i].due if i < len(pending) else end
            with _enter("wait_arrival"):
                time.sleep(max(0.0, min(nxt, end) - clock()))
    return t0, clock()


def submit(eng, r, clock, by_id=None):
    r.submitted = clock()
    try:
        r.request_id = eng.submit(r.prompt, max_new_tokens=r.max_new)
    except ValueError:
        r.failed = True
        return
    if by_id is not None:
        by_id[r.request_id] = r


def sample_for_check(reqs, check: dict, loop: str, seed: int) -> list:
    """The requests whose served tokens the reference scores: the longest
    and then others drawn from the seed, up to `sample_requests` or until
    `sample_tokens` served tokens are in."""
    if loop == "closed":
        pool = [r for r in reqs if r.tokens]
    else:
        pool = [r for r in reqs if len(r.tokens) >= r.max_new]
    if not pool:
        return []
    rng = np.random.default_rng(seed ^ 0xC0FFEE)
    longest = max(pool, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in pool if r is not longest]
    order = [rest[i] for i in rng.permutation(len(rest))]
    out, served = [longest], len(longest.tokens)
    for r in order:
        if (len(out) >= check["sample_requests"]
                or served >= check.get("sample_tokens", 0) > 0):
            break
        out.append(r)
        served += len(r.tokens)
    return out


def score(ref, w, spec, sample, pad_to, *, control: bool) -> dict:
    """The gap, in units of the reference row's standard deviation, by which
    a served token's logit lies below the reference's best, at every
    compared position: its mean (the number compared) and its widest; with
    `control`, the same for the token the fp8 forward puts first."""
    gaps, ctl = [], []
    for r in sample:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        pos = np.arange(len(r.prompt) - 1, len(seq))
        served = np.asarray(r.tokens, np.int32)
        rows = ref.hidden_rows(w, spec, seq, pos, pad_to=pad_to)
        st = ref.head_stats(w, spec, rows, served)
        gaps.append((st["best"] - st["got"]) / st["sd"])
        if control:
            rows8 = ref.hidden_rows(w, spec, seq, pos, pad_to=pad_to, fp8=True)
            top8 = ref.head_stats(w, spec, rows8, served, fp8=True)["top"]
            st8 = ref.head_stats(w, spec, rows, top8)
            ctl.append((st8["best"] - st8["got"]) / st8["sd"])
            del rows8
        del rows
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    out = {"mean_gap_sd": float(g.mean()) if g.size else float("inf"),
           "widest_gap_sd": float(g.max()) if g.size else float("inf"),
           "tokens_compared": int(g.size)}
    if control:
        c = np.concatenate(ctl)
        out["control_mean_gap_sd"] = float(c.mean())
        out["control_widest_gap_sd"] = float(c.max())
    return out


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             traced: bool, *, t_start: float, require_tpu: bool = True,
             control: bool = False, keep_trace: str | None = None,
             log=sys.stderr, mix_override: dict | None = None,
             scored: bool = True) -> dict:
    """Run one cell and return its result line as a dict. `mix_override`
    and `scored=False` (no reference check) serve the rate sweep
    (bench/sweep.py)."""
    clock = time.perf_counter
    bench = load_json(root / "BENCHMARK.json")
    cell, conf_entry = find_cell(bench, workload)
    conf = load_json(root / conf_entry["file"])
    data = root / "bench"
    mix = {**load_json(data / "traffic" / f"{cell['traffic']}.json"),
           **(mix_override or {})}
    devs, peaks = device_info(require_tpu, cell["chips"])

    import jax
    src = str(root / "src")
    if os.path.isdir(src) and src not in sys.path:
        sys.path.insert(0, src)
    from repro.serve.engine import Engine, ServeConfig

    arch = conf["bench"]["arch"]
    ref = load_module(data / "reference" / f"{arch}.py")
    adp = load_module(data / "adapters" / f"{arch}.py")
    serve = mix["serve"]
    nsel = topn(conf["bench"]["had"], serve["max_len"])
    spec = ref.spec_from_config(conf, nsel)
    cfg = adp.model_config(conf)

    with _enter("setup"):
        params = jax.jit(lambda k: adp.convert(ref.init_weights(k, spec), cfg))(
            ref.seed_key(seed))
        jax.block_until_ready(params)
        print(f"# weights on device: {clock() - t_start:.1f} s", file=log, flush=True)
        scfg = ServeConfig(max_len=serve["max_len"],
                           batch_slots=serve["batch_slots"], binary=True,
                           topn=nsel, prefill_chunk=serve["prefill_chunk"],
                           paged=True, page_size=serve["page_size"])
        eng = Engine(cfg, params, scfg)
        reqs, warm = traffic.make_requests(mix, seed=seed, seconds=seconds,
                                           vocab=conf["vocab_size"])
        tc = TokenClock(clock)
        eng.scheduler.token_sink = tc.sink
        if warm:
            for r in warm:
                submit(eng, r, clock)
            eng.run_pipelined()
        if mix["loop"] == "closed":
            for r in reqs:
                submit(eng, r, clock, tc.by_id)
            steps = 0
            while not all(r.tokens for r in reqs if not r.failed):
                step(eng, tc)
                steps += 1
                if steps % 16 == 0:
                    done = sum(1 for r in reqs if r.tokens)
                    print(f"# set-up: {clock() - t_start:.1f} s, {steps} steps, "
                          f"{done}/{len(reqs)} sessions prefilled", file=log,
                          flush=True)

    compiles = _count_compiles()
    stats0 = dict(eng.stats)
    pipe0 = eng.overlap_stats()
    trace_dir = None
    if traced:
        trace_dir = str(root / ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host spans (TraceAnnotation) without the per-call Python tracer,
        # which would slow the host loop being measured
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = clock() - t_start
    gc.callbacks.append(tc.gc_log)
    with _enter("window"):
        w0, w1 = drive(eng, reqs, mix, seconds, tc)
    gc.callbacks.remove(tc.gc_log)
    if traced:
        jax.profiler.stop_trace()
    n_compiles = compiles()
    stats1 = dict(eng.stats)
    pipe1 = eng.overlap_stats()
    step(eng, tc, "flush")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:cell["chips"]])

    tr = None
    if traced:
        from bench import devtrace
        tr = devtrace.load(devtrace.find_xplane(trace_dir))
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = Run(cell=cell, conf=conf, mix=mix, spec=spec, nsel=nsel,
              peaks=peaks, seconds=seconds, window_start=w0, window_end=w1,
              setup_s=setup_s, requests=reqs,
              counters={k: stats1[k] - stats0.get(k, 0) for k in stats1
                        if isinstance(stats1[k], (int, float))},
              sched={"schedule_s": pipe1["schedule_s"] - pipe0["schedule_s"],
                     "steps": pipe1["pipelined_steps"] - pipe0["pipelined_steps"]},
              trace=tr)
    metrics = {}
    for m in metrics_for(bench, workload, traced):
        reader = load_module(data / "metrics" / f"{m['name']}.py")
        v = reader.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # free the program's state before the reference runs
    eng.scheduler.token_sink = None
    del eng, params
    gc.collect()
    if not scored:
        return {"metrics": metrics, "run": run}
    sample = sample_for_check(reqs, mix["check"], mix["loop"], seed)
    t_ref = clock()
    w = ref.make_weights(seed, spec)
    pad_to = -(-serve["max_len"] // 512) * 512
    got = score(ref, w, spec, sample, pad_to, control=control)
    ref_s = clock() - t_ref

    attempted = sum(1 for r in reqs if r.in_window and (
        mix["loop"] == "closed" or r.due <= w1 - w0))
    failed = sum(1 for r in reqs if r.failed)
    limit = float(mix["check"]["mean_gap_sd"])
    correct = bool(sample) and got["mean_gap_sd"] <= limit
    checks = {"mean_gap_sd": {"value": got["mean_gap_sd"], "limit": limit}}
    if control:
        checks["control_mean_gap_sd"] = {
            "value": got["control_mean_gap_sd"], "limit": limit}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics,
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs),
                       "memory_peak_bytes": int(mem)}}
    if tr is not None:
        from bench import devtrace
        lo, hi = devtrace.window(tr)
        line["device"]["busy_s"] = devtrace.busy_seconds(tr, lo, hi)
        line["device"]["window_s"] = hi - lo
        line["breakdown"] = devtrace.breakdown(tr, lo, hi)
        runs = sorted(((m.end - m.start, m.start - lo) for m in tr.modules
                       if lo <= m.start < hi), reverse=True)[:3]
        gap = max(devtrace.idle_gaps(tr, lo, hi), key=lambda g: g[2],
                  default=("none", lo, 0.0))
        print("# trace: longest program executions "
              + ", ".join(f"{d:.3f} s at {t:.1f} s" for d, t in runs)
              + f"; longest idle gap {gap[2]:.3f} s at {gap[1] - lo:.1f} s"
              f" ({gap[0]})", file=log)
    line["check"] = checks
    print(f"# window {w1 - w0:.3f} s, compiles in window {n_compiles}, "
          f"decode steps {run.counters.get('decode_steps')}, prefill chunks "
          f"{run.counters.get('prefill_chunks')}, requests sampled "
          f"{len(sample)}, tokens compared {got['tokens_compared']}, "
          f"reference {ref_s:.1f} s, widest gap {got['widest_gap_sd']!r}"
          + (f", control widest gap {got['control_widest_gap_sd']!r}"
             if control else ""), file=log)
    print(f"# host: {tc.host_summary(w0, w1)}", file=log)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=log)
    return line


def _count_compiles():
    """Counts backend compilations from now on; call the result to read."""
    import jax
    box = [0]

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    return lambda: box[0]
