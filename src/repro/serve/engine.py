"""Continuous-batching serving engine over the HAD inference path.

The engine is a thin compatibility facade over an explicit
scheduler/executor split (vLLM-style):

  * :class:`repro.serve.scheduler.Scheduler` — pure host-side *policy*:
    the request queue, slot metadata, `BlockAllocator` / `PrefixCache` /
    `SwapPool` bookkeeping, admission order, the prefill budget, victim
    selection and reclaim ordering. `schedule()` emits a frozen
    `SchedulePlan` (device-free, unit-testable with no params or caches).
  * :class:`repro.serve.runner.ModelRunner` — *execution*: the jitted
    serve step, cache pools, sampling, and swapped pages' contents. It
    executes a plan verbatim and returns the sampled tokens.
  * `Engine.step()` is exactly
    `commit(plan, wait(execute_async(schedule())))`.

Serving semantics (unchanged public contract):

  * `submit()` enqueues a `Request` (prompt of any length, per-request
    sampling params / stop conditions) at any time.
  * `step()` ADMITS queued requests into free slots (metadata only),
    spends its prefill token budget (`prefill_chunk`) on at most ONE
    chunk of the earliest-admitted prefilling slot — written in place
    into that slot's rows of the shared cache via per-slot
    `pos`/`active`/`n_valid` masking — then runs ONE batched ragged
    decode step for every decoding slot. Residents emit tokens *between*
    a long admission's prefill chunks; tail chunks are padded so every
    prompt length shares one prefill trace plus one decode trace.
  * With `ServeConfig(paged=True)` caches are shared page pools behind
    per-slot block tables; pool pressure reclaims LRU prefix pages
    first, then evicts a victim — by **page-aligned swap-out** to a
    bounded host pool when `swap_pages > 0` (pages gathered/freed,
    restored verbatim on re-admission: zero tokens re-prefilled, rng and
    generated tokens preserved) and by recompute preemption otherwise.
  * With `prefix_cache=True` admission maps the longest cached
    page-aligned prompt prefix into the block table and skips its
    prefill entirely.
  * Models with SSM or cross-attention layers serve all of the above
    through pooled recurrent/cross state (`serve/statepool.py`): one
    state entry per resident slot plus checkpoint entries captured at
    KV-page boundaries during chunked prefill, so prefix hits restore
    the matched boundary's recurrent state and swap-outs gather/restore
    the state entry atomically with the KV pages.
  * `run()` loops until the queue and all slots are drained.

The binary path stores the K cache bit-packed (16x smaller than bf16) and
top-N-sparsifies the V accumulation — the paper's long-context serving
story end-to-end. All positions/lengths are int32 (the kernels' dtype).

The low-level `prefill()` / `decode()` methods remain for lockstep use
(uniform-length batches driven by hand) and for tests; `generate()` is a
convenience that routes through the scheduler.

Every step phase runs under a `jax.profiler.TraceAnnotation` named
`serve.<phase>` (the engine's: schedule, dispatch, land, commit, resolve,
commit_structural; the runner's: prefill_chunk, decode, swap,
device_wait, sample), so a profiler trace puts each idle stretch of the
device down to a host phase; with no profiler running a span costs under
a microsecond. The registry's always-on host counters (host_schedule_s,
host_overlap_s, host_exposed_s, host_sample_s, admitted, admit_wait_s,
first_chunks, first_chunk_wait_s) are read from the same step.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any

import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.models.config import ModelConfig
from repro.serve.paged import BlockAllocator, PrefixCache, SwapPool  # noqa: F401 (re-export)
from repro.serve.runner import ModelRunner, _chunk_extra, _sample_token
from repro.serve.scheduler import (FinishedRequest, Request, SamplingParams,
                                   SchedulePlan, Scheduler, ServeConfig)
from repro.serve.statepool import StatePool
from repro.serve.telemetry import RequestMetrics, Telemetry  # noqa: F401
from repro.serve.validate import (state_layer_positions,
                                  validate_serve_features,
                                  validate_serve_mesh)

__all__ = ["Engine", "FinishedRequest", "Request", "RequestMetrics",
           "SamplingParams", "SchedulePlan", "Scheduler", "ModelRunner",
           "ServeConfig", "StatePool", "Telemetry"]


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-uncommitted pipelined step: the resolved plan,
    the runner's pending handle, and the host timestamps needed to stamp
    its flight-recorder event once it lands."""
    plan: SchedulePlan
    pending: Any
    launch_ts: float                   # execute_async dispatch time
    sched_s: float                     # host time spent building the plan
    structural_s: float                # host time of commit_structural
    step: int                          # the step index of its schedule()


class Engine:
    def __init__(self, cfg: ModelConfig, params: dict, scfg: ServeConfig,
                 telemetry: Telemetry | None = None):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        # model-pattern x feature coherence lives in ONE shared helper
        # (serve/validate.py) — the runner re-checks the same rules
        validate_serve_features(cfg.layer_pattern, scfg)
        # tensor-parallel coherence (ServeConfig.mesh): fail before the
        # runner builds a shard_map over an indivisible head count
        validate_serve_mesh(cfg, scfg)
        state_layers = (len(state_layer_positions(cfg.layer_pattern))
                        if scfg.paged else 0)
        # when a telemetry hub is attached, its registry IS the engine's
        # stats (one declared schema shared by scheduler, runner, and the
        # request-latency histograms); disabled costs one None check per
        # hook site
        self.telemetry = telemetry
        self.scheduler = Scheduler(
            scfg, stats=(telemetry.registry if telemetry else None),
            state_layers=state_layers)
        self.scheduler.telemetry = telemetry
        self.runner = ModelRunner(cfg, params, scfg,
                                  stats=self.scheduler.stats)
        self.runner.telemetry = telemetry
        self.n = self.runner.n
        self.chunk = self.scheduler.chunk
        # the double buffer: at most ONE dispatched-but-uncommitted step
        self._inflight: _Inflight | None = None
        # numbers schedule() calls: the step index on the serve.* spans
        self._step_idx = itertools.count()
        # request_id -> perf_counter stamp, held until the request's first
        # admission (submit time) and then its first prefill chunk's
        # dispatch (first admission time): the admit / first-chunk waits
        self._submitted: dict[int, float] = {}
        self._admitted: dict[int, float] = {}

    # ------------------------------------------------------------------
    # facade: shared state lives on the scheduler (host) / runner (device)
    # ------------------------------------------------------------------
    @property
    def stats(self) -> dict:
        return self.scheduler.stats

    @property
    def slots(self):
        return self.scheduler.slots

    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def allocator(self) -> BlockAllocator | None:
        return self.scheduler.allocator

    @property
    def prefix(self) -> PrefixCache | None:
        return self.scheduler.prefix

    @property
    def swap(self) -> SwapPool | None:
        return self.scheduler.swap

    @property
    def statepool(self) -> StatePool | None:
        return self.scheduler.statepool

    @property
    def block_tables(self):
        return self.scheduler.block_tables

    @property
    def state_tables(self):
        return self.scheduler.state_tables

    @property
    def max_blocks(self) -> int:
        return self.scheduler.max_blocks

    @property
    def page(self) -> int:
        return self.scheduler.page

    @property
    def caches(self) -> dict:
        return self.runner.caches

    @caches.setter
    def caches(self, value: dict) -> None:
        self.runner.caches = value

    @property
    def _step(self):
        return self.runner._step

    @property
    def _resume(self) -> dict:
        return self.scheduler._resume

    # scheduler internals kept addressable for tests / introspection
    def _admit(self, i: int, req: Request) -> None:
        self.scheduler._admit(i, req)

    def _pop_next(self) -> Request:
        return self.scheduler._pop_next()

    def _pick_victim(self) -> int:
        return self.scheduler._pick_victim()

    def _register_full_pages(self, i: int, slot) -> None:
        self.scheduler._register_full_pages(i, slot)

    # ------------------------------------------------------------------
    # scheduler API
    # ------------------------------------------------------------------
    def submit(self, tokens: np.ndarray | Request, max_new_tokens: int = 16,
               *, eos_token: int | None = None,
               sampling: SamplingParams | None = None,
               extra: dict | None = None, priority: str = "batch") -> int:
        """Enqueue a request; returns its request_id. May be called at any
        time — admission happens at the next `step()` if a slot is free."""
        rid = self.scheduler.submit(tokens, max_new_tokens,
                                    eos_token=eos_token, sampling=sampling,
                                    extra=extra, priority=priority)
        self._submitted[rid] = time.perf_counter()
        return rid

    def step(self) -> list[FinishedRequest]:
        """One synchronous scheduler step — a thin wrapper over the same
        primitives the pipelined path uses: execution is
        `wait(execute_async(plan))` and `commit()` is
        `commit_structural(plan)` + `commit_tokens(plan, results)`, just
        composed back-to-back with no overlap. Returns newly finished
        requests (any in-flight pipelined step is landed first — mixing
        the two stepping APIs never reorders commits).

        With telemetry attached, each phase is timed host-side (monotonic
        clock) and the plan is recorded as one flight-recorder step event;
        `Telemetry(fence=True)` blocks on the cache pools before the
        execute->commit stamp so execute time is device time, not
        dispatch time."""
        finished = self.flush()
        tel = self.telemetry
        clock = self._clock()
        idx = next(self._step_idx)
        with _span("serve.schedule", step=idx):
            t0 = clock()
            plan = self.scheduler.schedule()
            t1 = clock()
        self._stamp_admissions(plan)
        results = self._land(self._dispatch(plan), idx)
        if tel is not None and tel.fence:
            self.runner.sync()
        t2 = clock()
        with _span("serve.commit"):
            finished += self.scheduler.commit(plan, results)
        t3 = clock()
        self.runner.close_exposed()
        if tel is not None:
            tel.record_step(plan, timings={"schedule": t1 - t0,
                                           "execute": t2 - t1,
                                           "commit": t3 - t2,
                                           "fenced": tel.fence},
                            pool=self.scheduler.watermarks())
        return finished

    # ------------------------------------------------------------------
    # pipelined stepping (double-buffered schedule/execute overlap)
    # ------------------------------------------------------------------
    def _clock(self):
        return self.telemetry.clock if self.telemetry else time.perf_counter

    def step_pipelined(self) -> list[FinishedRequest]:
        """One double-buffered step: build plan N+1 while step N is still
        in flight on device, then land step N, resolve plan N+1 against
        its committed tokens, and dispatch it.

        Per iteration: `schedule()` runs first — the whole host-side
        policy pass overlaps the previous step's device execution (that
        interval is the recorded `overlap`). Only then does the host sync
        on step N (`runner.wait`), token-commit it, rebind plan N+1's
        stale decode inputs (`resolve_plan`), dispatch it
        (`execute_async`), and apply its structural commit. Outputs are
        bit-identical to `step()` — scheduling *policy* may diverge
        (admissions and preemptions see token effects one step later),
        which the standing warm==cold / swapped==unpreempted pins
        guarantee is output-invariant. Returns requests finished by the
        step that landed."""
        clock = self._clock()
        idx = next(self._step_idx)
        with _span("serve.schedule", step=idx):
            t0 = clock()
            plan = self.scheduler.schedule()
            t1 = clock()
        self.stats["host_schedule_s"] += t1 - t0
        self._stamp_admissions(plan)
        finished = (self._complete_inflight((t0, t1))
                    if self._inflight is not None else [])
        if not (plan.admissions or plan.swap_ins or plan.reclaims
                or plan.prefill or plan.decode):
            self.runner.close_exposed()
            return finished            # nothing to dispatch — don't track
        with _span("serve.resolve"):
            plan = self.scheduler.resolve_plan(plan)
        launch = clock()
        pending = self._dispatch(plan)
        s0 = clock()
        with _span("serve.commit_structural"):
            self.scheduler.commit_structural(plan)
        s1 = clock()
        self._inflight = _Inflight(plan, pending, launch, t1 - t0, s1 - s0,
                                   idx)
        self.stats["pipelined_steps"] += 1
        self.runner.close_exposed()
        return finished

    def _stamp_admissions(self, plan: SchedulePlan) -> None:
        """Count the plan's first admissions (a resume after a preemption
        was counted at its first) and the seconds each waited since
        `submit()`."""
        now = time.perf_counter()
        for adm in plan.admissions:
            rid = adm.request.request_id
            t = self._submitted.pop(rid, None)
            if t is not None:
                self.stats["admitted"] += 1
                self.stats["admit_wait_s"] += now - t
                self._admitted[rid] = now

    def _dispatch(self, plan: SchedulePlan):
        """`execute_async(plan)` under its span; counts each request whose
        first prefill chunk it carries, and the seconds since that
        request's first admission."""
        now = time.perf_counter()
        for ch in plan.prefill:
            t = self._admitted.pop(ch.request.request_id, None)
            if t is not None:
                self.stats["first_chunks"] += 1
                self.stats["first_chunk_wait_s"] += now - t
        with _span("serve.dispatch"):
            return self.runner.execute_async(plan)

    def _land(self, pending, idx: int) -> dict[int, list[int]]:
        with _span("serve.land", step=idx):
            return self.runner.wait(pending)

    def _complete_inflight(self, overlap_interval: tuple[float, float]
                           | None = None) -> list[FinishedRequest]:
        """Land the in-flight step: host-sync its sampled tokens, token-
        commit them, and stamp its flight-recorder event. The event's
        `overlap` is how much of the given host interval (the NEXT plan's
        schedule phase) fell inside this step's device window
        [dispatch, wait-end]."""
        inflight = self._inflight
        self._inflight = None
        results = self._land(inflight.pending, inflight.step)
        clock = self._clock()
        t2 = clock()
        with _span("serve.commit"):
            finished = self.scheduler.commit_tokens(inflight.plan, results)
        t3 = clock()
        execute_s = t2 - inflight.launch_ts
        overlap = 0.0
        if overlap_interval is not None:
            o0, o1 = overlap_interval
            overlap = max(0.0, min(o1, t2) - max(o0, inflight.launch_ts))
        self.stats["host_overlap_s"] += overlap
        if self.telemetry is not None:
            self.telemetry.record_step(
                inflight.plan,
                timings={"schedule": inflight.sched_s,
                         "execute": execute_s,
                         "commit": inflight.structural_s + (t3 - t2),
                         "fenced": False,
                         "overlap": overlap,
                         "pipelined": True},
                pool=self.scheduler.watermarks())
        return finished

    def flush(self) -> list[FinishedRequest]:
        """Land any in-flight pipelined step (no-op when none). Called on
        entry to every synchronous `step()`."""
        if self._inflight is None:
            return []
        finished = self._complete_inflight()
        self.runner.close_exposed()
        return finished

    def overlap_stats(self) -> dict:
        """Aggregate pipelined-overlap accounting: seconds of host
        schedule time total vs hidden under device windows, and the
        resulting overlap fraction (the acceptance metric for the
        double buffer), read from the registry's counters."""
        sched, over = self.stats["host_schedule_s"], self.stats["host_overlap_s"]
        return {"schedule_s": sched, "overlap_s": over,
                "pipelined_steps": self.stats["pipelined_steps"],
                "overlap_frac": over / sched if sched > 0 else 0.0}

    def run_pipelined(self) -> dict[int, np.ndarray]:
        """`run()` over the double-buffered step: drains the queue, all
        slots, AND the in-flight step; returns request_id -> tokens."""
        out: dict[int, np.ndarray] = {}
        while (self.queue or any(s.request is not None for s in self.slots)
               or self._inflight is not None):
            for fr in self.step_pipelined():
                out[fr.request_id] = fr.tokens
        for fr in self.scheduler._drain_finished():
            out[fr.request_id] = fr.tokens
        return out

    def pop_finished_metrics(self) -> list[RequestMetrics]:
        """Drain the lifecycle records of requests that finished since the
        last call (empty when telemetry is disabled)."""
        return (self.telemetry.pop_finished()
                if self.telemetry is not None else [])

    def check(self) -> None:
        """Debug probe: run every pool invariant check (BlockAllocator /
        SwapPool / StatePool accounting + slot <-> block-table
        cross-checks) in one call. On failure, the flight recorder is
        dumped to the telemetry trace file (when one is configured)
        before the AssertionError propagates."""
        try:
            self.scheduler.check()
        except Exception as e:
            tel = self.telemetry
            if tel is not None and tel.trace_file:
                tel.recorder.dump(
                    tel.trace_file, clock=tel.clock,
                    extra_events=[{"kind": "check", "ts": tel.clock(),
                                   "ok": False, "error": str(e)}],
                    note=f"invariant failure dump: {e}")
            raise

    def dump_trace(self, path: str | None = None, *,
                   requests=()) -> int:
        """Write the flight-recorder ring buffer as JSONL (meta header,
        buffered step events, live + undrained request records, and a
        check event from an auto-run `check()`). Records already drained
        via `pop_finished_metrics()` can be handed back through
        `requests` to appear in the dump. Returns the number of events
        written."""
        tel = self.telemetry
        if tel is None:
            raise RuntimeError("dump_trace requires an Engine telemetry "
                               "hub (Engine(..., telemetry=Telemetry()))")
        path = path if path is not None else tel.trace_file
        if path is None:
            raise RuntimeError("no trace path: pass one or set "
                               "Telemetry(trace_file=...)")
        ok, err = True, ""
        try:
            self.scheduler.check()
        except AssertionError as e:
            ok, err = False, str(e)
        extra = [m.to_event() for m in requests]
        extra += [m.to_event() for m in tel.live_requests]
        extra += [m.to_event() for m in tel._finished]
        extra.append({"kind": "check", "ts": tel.clock(), "ok": ok,
                      "error": err})
        n = tel.recorder.dump(path, extra_events=extra, clock=tel.clock)
        if not ok:
            raise AssertionError(err)
        return n

    def run(self) -> dict[int, np.ndarray]:
        """Step until queue and slots drain; returns request_id -> tokens."""
        out: dict[int, np.ndarray] = {}
        while self.queue or any(s.request is not None for s in self.slots):
            for fr in self.step():
                out[fr.request_id] = fr.tokens
        for fr in self.scheduler._drain_finished():
            out[fr.request_id] = fr.tokens
        return out

    def reset_stats(self) -> None:
        """Zero the counters (e.g. after a warm-up pass, so benchmark stats
        don't double-count); watermarks restart at current occupancy.
        Telemetry request records from before the reset are dropped the
        same way — the next `pop_finished_metrics()` only sees requests
        finishing after this call."""
        self.scheduler.reset_stats()
        if self.telemetry is not None:
            self.telemetry.pop_finished()

    # ------------------------------------------------------------------
    # low-level lockstep API (uniform batches, hand-driven)
    # ------------------------------------------------------------------
    def prefill(self, tokens: np.ndarray, extra: dict | None = None):
        """Uniform-length batched prefill of ALL slots at once.

        tokens: [batch_slots, S]. Resets every slot (any resident requests
        are dropped — their caches, sampling rngs and pending tokens are
        cleared, not just their bindings). Raises if requests are still
        QUEUED: silently discarding unstarted submissions is never what
        the caller meant — drain the scheduler first. Returns
        last-position logits [batch_slots, V]. Shares the padded-chunk
        trace with scheduler admissions."""
        if self.queue:
            raise RuntimeError(
                f"lockstep prefill() with {len(self.queue)} queued "
                f"request(s): it would silently orphan them — drain the "
                f"scheduler (run()) or don't mix the APIs")
        tokens = np.asarray(tokens, np.int32)
        b, s = tokens.shape
        assert b == self.scfg.batch_slots, (b, self.scfg.batch_slots)
        # dropping residents must drop ALL their scheduler state — stale
        # `generated`/`next_token`/`rng` leaked into the next occupant's
        # bookkeeping, and a preempted resident's resume/swap entry would
        # outlive the request it belonged to; the runner likewise rebuilds
        # its pools from zeros and drops swapped page contents
        self._inflight = None          # lockstep resets drop pending work
        self._admitted.clear()
        self.scheduler.reset_for_lockstep()
        self.runner.reset_caches()
        if self.scfg.paged:
            for i in range(b):  # lockstep never preempts: all-or-error
                self.scheduler.lockstep_alloc(i, s)
        logits = None
        lo = 0
        while lo < s:
            hi = min(lo + self.chunk, s)
            nv = hi - lo
            padded = np.zeros((b, self.chunk), np.int32)
            padded[:, :nv] = tokens[:, lo:hi]
            logits = self.runner.prefill_step(
                padded, _chunk_extra(extra, s, lo, hi, self.chunk),
                np.full((b,), lo, np.int32), np.ones((b,), bool),
                np.full((b,), nv, np.int32), self.block_tables,
                self.state_tables)
            lo = hi
        for slot in self.slots:
            slot.length = s
            slot.prefill_pos = s
        return logits[:, -1, :self.cfg.vocab_size]  # logits_mode="last": S==1

    def decode(self, tokens: np.ndarray):
        """One ragged decode step for every slot. tokens: [batch_slots] int.
        Slots may sit at different positions (per-slot `pos` vector)."""
        pos = np.array([s.length for s in self.slots], np.int32)
        if (pos >= self.scfg.max_len).any():
            raise ValueError(f"slot cache full (max_len={self.scfg.max_len})")
        b = self.scfg.batch_slots
        if self.scfg.paged:
            for i in range(b):  # lockstep never preempts: all-or-error
                self.scheduler.lockstep_alloc(i, int(pos[i]) + 1)
        logits = self.runner.decode_step(np.asarray(tokens, np.int32), pos,
                                         np.ones((b,), bool),
                                         self.block_tables,
                                         self.state_tables)
        for slot in self.slots:
            slot.length += 1
        return logits[:, 0, :self.cfg.vocab_size]

    @property
    def lengths(self) -> np.ndarray:
        """Per-slot valid cache lengths, int32 (kernel dtype)."""
        return self.scheduler.lengths

    # ------------------------------------------------------------------
    def generate(self, prompts, steps: int,
                 extra: dict | None = None) -> np.ndarray:
        """Greedy generation through the scheduler.

        prompts: [R, S] array or a list of R 1-D prompts of any lengths
        (R may exceed batch_slots — overflow requests queue and re-fill
        slots as earlier ones finish). Returns [R, steps] tokens in
        submission order."""
        rows = [np.asarray(p, np.int32) for p in prompts]
        ids = []
        for i, row in enumerate(rows):
            req_extra = None
            if extra is not None:
                req_extra = {k: np.asarray(v)[i:i + 1] for k, v in extra.items()}
            ids.append(self.submit(row, max_new_tokens=steps,
                                   extra=req_extra))
        results = self.run()
        return np.stack([results[rid] for rid in ids], axis=0)
