"""Serving execution layer: the ModelRunner.

The runner is the *data plane* of the serving stack: it owns the jitted
serve step, the KV cache pools, sampling execution, and the host-side
contents of swapped-out pages — and nothing else. It is completely
stateless about requests: every step it executes exactly the frozen
:class:`~repro.serve.scheduler.SchedulePlan` the Scheduler handed it
(positions, active rows, chunk ranges, block-table snapshot, reclaim and
swap actions are all decided at plan time) and returns the per-slot
sampled tokens. All bookkeeping driven by those tokens — stop conditions,
page registration, slot frees — happens back in `Scheduler.commit`.

Execution order within one plan (the order that makes page recycling
safe):

  1. swap-in scatters — restore swapped requests' page contents (and,
     for hybrid models, their pooled state entry) into freshly allocated
     device pages/entries (plan-time allocation precedes every reclaim,
     so these can never be claimed by a same-plan swap-out victim);
  2. swap-out gathers — copy each victim's pages AND state entry to host
     BEFORE any planned write can recycle them;
  3. admission state init — zero each fresh/recompute admission's live
     state entry (so a re-filled slot never inherits the previous
     occupant's h/conv/cross state), or copy a prefix-matched boundary's
     checkpoint entry into it ("swap" resumes skip this: their entry is
     restored by step 1);
  4. prefill chunks, in plan order, sampling each completed prompt's
     first token from the chunk's last-valid logits; a chunk with a
     planned `state_ckpt` is followed by a live-entry -> checkpoint-entry
     copy (the recurrent state at the chunk's page-aligned frontier);
  5. one batched ragged decode over the plan's decode set (minus slots
     whose just-sampled first token hit eos — the one stop condition
     only execution can observe).

The swap transfers are one-off gathers/scatters per eviction (one
indexed take / indexed update per cache leaf) — they never touch the
jitted step, so the one-prefill-trace + one-decode-trace pin holds.
Swap-out gathers are *asynchronous*: the device-side indexed take is
dispatched (capturing the pre-recycle page contents by data dependency)
and the D2H copy started with ``copy_to_host_async``, but the host only
blocks for the bytes at the next `wait()`/`sync()` — the transfer rides
under the same step's decode work.

Plan execution splits the same way: `execute_async(plan)` dispatches
every stage and returns a :class:`_PendingStep` whose decode logits are
still in flight; `wait(pending)` is the one host sync point, where the
decode tokens are sampled and pending swap bytes land (per-slot tokens in
emission order: a slot completing prefill and decoding in the same step
yields two). A pipelined engine schedules plan N+1 between the two; the
synchronous `Engine.step()` calls them back to back.

Sampling is split in three so a profiler trace tells device time from
host time: the eager slice of the logits rows is dispatched, the host
blocks on it (span `serve.device_wait`), then fetches the rows and draws
the tokens (`serve.sample`, counted in `host_sample_s`). From that
block's return until the next step program has been dispatched (or the
engine call returns, `close_exposed()`) the device has nothing of this
engine's to run: those host seconds are `host_exposed_s`.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.core import hamming
from repro.distributed import sharding as shd
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serve.paged import pages_needed
from repro.serve.scheduler import SamplingParams, SchedulePlan, ServeConfig
from repro.serve.telemetry import SERVE_COUNTERS, MetricsRegistry
from repro.serve.validate import (mesh_model_size, resolve_state_pages,
                                  state_layer_positions,
                                  validate_serve_features,
                                  validate_serve_mesh)

Array = jax.Array


def _sample_token(logits: np.ndarray, sp: SamplingParams, rng) -> int:
    if sp.temperature <= 0.0:
        return int(np.argmax(logits))
    l = logits.astype(np.float64) / sp.temperature
    if 0 < sp.top_k < l.size:
        # exactly top_k survive; ties at the k-th value break by lowest
        # index (a plain `l >= kth` keeps every tied logit, sampling from
        # outside the requested top-k). O(V) partition — no full-vocab
        # sort on the per-token host path.
        kth = np.partition(l, -sp.top_k)[-sp.top_k]
        above = l > kth
        ties = np.flatnonzero(l == kth)[:sp.top_k - int(above.sum())]
        masked = np.full_like(l, -np.inf)
        masked[above] = l[above]
        masked[ties] = kth
        l = masked
    l -= l.max()
    p = np.exp(l)
    p /= p.sum()
    return int(rng.choice(l.size, p=p))


def _chunk_extra(extra: dict | None, s: int, lo: int, hi: int, chunk: int,
                 *, batch: int | None = None, row: int | None = None) -> dict:
    """Route extra model inputs into the padded [lo, hi) prefill chunk.

    `image_embeds` fills the (static, persisted) cross cache — first chunk
    only. Sequence-aligned arrays (axis 1 == prompt length, e.g. `frames`)
    are sliced to the chunk and zero-padded to `chunk` so every chunk
    shape shares one trace. Anything else rides with the first chunk.
    With `row`/`batch` set (in-slot admission), batch-1 request arrays are
    scattered into row `row` of a zeros [batch, ...] array — rows of other
    slots are masked out of cache updates anyway.
    """
    out: dict[str, Any] = {}
    for key, val in (extra or {}).items():
        arr = jnp.asarray(val)
        if key != "image_embeds" and arr.ndim >= 2 and arr.shape[1] == s:
            arr = arr[:, lo:hi]
            if hi - lo < chunk:
                widths = [(0, 0)] * arr.ndim
                widths[1] = (0, chunk - (hi - lo))
                arr = jnp.pad(arr, widths)
        elif lo != 0:
            continue
        if row is not None:
            full = jnp.zeros((batch,) + arr.shape[1:], arr.dtype)
            arr = full.at[row].set(arr[0])
        out[key] = arr
    return out


@dataclasses.dataclass
class _PendingStep:
    """An `execute_async` dispatch awaiting its host sync: prefill-sampled
    tokens are already final (the samples->same-step-decode handoff needs
    them on host), decode logits are still device-side. `wait()` samples
    the decode tokens and returns the merged per-slot results."""
    results: dict[int, list[int]]
    entries: list                      # decode entries pending sampling
    logits: Any = None                 # un-synced decode logits, or None


class ModelRunner:
    """Device-state owner and plan executor for one serving engine."""

    def __init__(self, cfg: ModelConfig, params: dict, scfg: ServeConfig,
                 stats: dict):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        # usually the Scheduler's registry (one shared schema across the
        # stack); a standalone runner adopts whatever it was handed —
        # undeclared counter keys raise instead of silently appearing
        self.stats = MetricsRegistry.adopt(stats)
        self.stats.declare_counters(SERVE_COUNTERS)
        # optional observability hub (set by the Engine)
        self.telemetry = None
        # optional hook: called (request_id, logits row [vocab] numpy)
        # with the logits of every sampled token, in sampling order
        self.logits_sink = None
        validate_serve_features(cfg.layer_pattern, scfg)
        validate_serve_mesh(cfg, scfg)
        # tensor-parallel serving (ServeConfig.mesh, model axis > 1): the
        # jitted step runs under shard_map with params head-sharded and
        # the KV pools sharded over the kv-head dim; everything host-side
        # (scheduler, swap accounting, telemetry, this runner's plan
        # bookkeeping) stays mesh-oblivious, and all counters stay
        # LOGICAL/aggregate so stats are identical across mesh sizes.
        self.mesh = getattr(scfg, "mesh", None)
        self._tp = mesh_model_size(scfg)
        self.n = scfg.topn if scfg.topn is not None else cfg.had.topn(scfg.max_len)
        self.chunk = max(1, min(scfg.prefill_chunk, scfg.max_len))
        self.page = scfg.page_size
        if scfg.paged:
            self.n_pages = (scfg.n_pages if scfg.n_pages is not None
                            else scfg.batch_slots
                            * pages_needed(scfg.max_len, self.page))
            # decode HBM traffic model (host-side, per attention
            # layer-instance x kv-head): bytes of one page of K (packed
            # bit-planes on the binary path, fp otherwise) and of V
            elem = jnp.empty((0,), cfg.dtype).dtype.itemsize
            self._page_v_bytes = self.page * cfg.dh * elem
            self._page_k_bytes = (hamming.packed_words(cfg.dh) * 4 * self.page
                                  if scfg.binary else self._page_v_bytes)
            self._attn_rows = (cfg.layer_pattern.count("A") * cfg.n_groups
                               * cfg.n_kv_heads)
        else:
            self.n_pages = 0
        # pooled recurrent/cross state: paged engines with SSM ('M') or
        # cross-attention ('C') layers keep that state in shared entry
        # pools addressed by the plan's state_tables (serve/statepool.py)
        self._state_positions = (state_layer_positions(cfg.layer_pattern)
                                 if scfg.paged else ())
        self.n_state_pages = (resolve_state_pages(scfg)
                              if self._state_positions else 0)
        self.caches = self._init_caches()
        # swapped-out contents, request_id -> {"kv": {cache key -> {leaf
        # name -> np [n_groups, k_pages, ...]}}, "state": {cache key ->
        # {leaf name -> np [n_groups, ...]}}} (accounting lives in the
        # scheduler's SwapPool; this is the data half)
        self._swap_store: dict[int, dict] = {}
        # request_ids whose swap-out gathers are still device-side arrays
        # with an async D2H in flight (finalized to numpy at wait()/sync())
        self._pending_swaps: list[int] = []
        # perf_counter stamp opening the current host-exposed stretch
        # (None while a program is in flight or outside an engine call)
        self._exposed_since: float | None = None

        if self._tp > 1:
            self._step = self._build_sharded_step()
        else:
            @functools.partial(jax.jit, static_argnames=("n", "binary",
                                                         "page_topn"))
            def _step(params, batch, caches, pos, active, n_valid,
                      block_tables, state_tables, *, n, binary, page_topn):
                return M.serve_step(params, batch, caches, cfg=cfg, pos=pos,
                                    n=n, binary=binary, logits_mode="last",
                                    active=active, n_valid=n_valid,
                                    block_tables=block_tables,
                                    page_topn=page_topn,
                                    state_tables=state_tables)
            self._step = _step

    def _build_sharded_step(self):
        """shard_map'd twin of the jitted step (exact-parity TP).

        The body sees LOCAL shards: a cfg with n_heads/n_kv_heads divided
        by the mesh model axis (head_dim pinned first — `dh` derives from
        d_model/n_heads when unset, which must not change), head-sharded
        wq/wk/wv + kv-head-sharded pool slices, and everything else
        replicated. Collectives are confined to serve_step (one context
        all_gather per attention layer, a page-score pmax, the final
        logits gather) so outputs stay bit-identical to the single-device
        step. Same static argnames -> the 1-prefill + 1-decode trace pin
        holds per mesh size.
        """
        from jax.sharding import PartitionSpec

        cfg, mesh, tp = self.cfg, self.mesh, self._tp
        self.params = jax.device_put(
            self.params, shd.serve_param_shardings(self.params, mesh))
        local_cfg = dataclasses.replace(
            cfg, head_dim=cfg.dh,
            n_heads=cfg.n_heads // tp,
            n_kv_heads=cfg.n_kv_heads // tp)
        param_ps = shd.serve_param_pspecs(self.params, mesh)
        cache_ps = shd.serve_cache_pspecs(self.caches, mesh)
        rep = PartitionSpec()

        @functools.partial(jax.jit, static_argnames=("n", "binary",
                                                     "page_topn"))
        def _step(params, batch, caches, pos, active, n_valid,
                  block_tables, state_tables, *, n, binary, page_topn):
            def body(params, batch, caches, pos, active, n_valid,
                     block_tables, state_tables):
                return M.serve_step(params, batch, caches, cfg=local_cfg,
                                    pos=pos, n=n, binary=binary,
                                    logits_mode="last", active=active,
                                    n_valid=n_valid,
                                    block_tables=block_tables,
                                    page_topn=page_topn,
                                    state_tables=state_tables,
                                    axis_name="model")
            fn = jax.shard_map(body, mesh=mesh,
                               in_specs=(param_ps, rep, cache_ps, rep, rep,
                                         rep, rep, rep),
                               out_specs=(rep, cache_ps),
                               check_vma=False)
            return fn(params, batch, caches, pos, active, n_valid,
                      block_tables, state_tables)
        return _step

    def cache_device_bytes(self) -> tuple[int, int]:
        """(logical_total, per_device) bytes of the attention KV caches.

        Under tensor-parallel serving each pool leaf's per-device
        footprint comes from its sharding's `shard_shape` — the kv-head
        dim shrinks 1/tp exactly (divisibility is validated), while block
        tables and every plan array stay replicated. Single-device the
        two numbers are equal."""
        total = per = 0
        for key in self._pool_keys():
            for leaf in self.caches[key].values():
                total += int(leaf.nbytes)
                shard = leaf.sharding.shard_shape(leaf.shape)
                per += int(np.prod(shard)) * leaf.dtype.itemsize
        return total, per

    def _init_caches(self) -> dict:
        scfg = self.scfg
        state_pages = self.n_state_pages if self._state_positions else None
        if scfg.paged:
            caches = M.init_caches(self.cfg, scfg.batch_slots, scfg.max_len,
                                   binary=scfg.binary, paged=True,
                                   n_pages=self.n_pages, page_size=self.page,
                                   state_pages=state_pages)
        else:
            caches = M.init_caches(self.cfg, scfg.batch_slots, scfg.max_len,
                                   binary=scfg.binary)
        if self._tp > 1:
            # head-shard the pools up front so the first step pays no
            # resharding transfer; eager swap-in scatters / state-entry
            # `.at[].set`s leave layouts for jit to restore, which it does
            # against these same specs
            caches = jax.device_put(
                caches, shd.serve_cache_shardings(caches, self.mesh))
        return caches

    def reset_caches(self) -> None:
        """Rebuild the cache pools from zeros (lockstep prefill contract)
        and drop swapped page contents — the pages they would restore into
        no longer exist."""
        self.caches = self._init_caches()
        self._swap_store.clear()
        self._pending_swaps.clear()

    def sync(self) -> None:
        """Block until every in-flight device write to the cache pools has
        landed — the fence behind `Telemetry(fence=True)`, separating
        device time from dispatch time in step phase timings."""
        self._finalize_swaps()
        jax.block_until_ready(self.caches)

    def close_exposed(self) -> None:
        """End the open host-exposed stretch, if any: the call that
        dispatches the next step program has returned (its argument
        transfers and launch are host work the device waits on), or the
        engine call returns."""
        if self._exposed_since is not None:
            self.stats["host_exposed_s"] += (time.perf_counter()
                                             - self._exposed_since)
            self._exposed_since = None

    def _sample_rows(self, sliced: Array, draws) -> list[int]:
        """Land one program's sliced logits and draw from them: block
        until the device has computed them (`serve.device_wait`), then
        fetch them and draw one token per (row, request, sampling, rng)
        of `draws` (`serve.sample`). The block's return opens the
        host-exposed stretch."""
        with _span("serve.device_wait"):
            sliced.block_until_ready()
        t0 = self._exposed_since = time.perf_counter()
        with _span("serve.sample", slots=len(draws)):
            rows = np.asarray(sliced).reshape(-1, sliced.shape[-1])
            toks = []
            for i, req, sp, rng in draws:
                if self.logits_sink is not None:
                    self.logits_sink(req.request_id, rows[i])
                toks.append(_sample_token(rows[i], sp, rng))
        self.stats["host_sample_s"] += time.perf_counter() - t0
        return toks

    # ------------------------------------------------------------------
    # low-level steps (shared by plan execution and the lockstep API)
    # ------------------------------------------------------------------
    def prefill_step(self, tokens: np.ndarray, extra: dict,
                     pos: np.ndarray, active: np.ndarray,
                     n_valid: np.ndarray,
                     block_tables: np.ndarray | None,
                     state_tables: np.ndarray | None = None) -> Array:
        """One padded prefill chunk through the jitted step: tokens
        [B, chunk] zero-padded, per-row pos/active/n_valid masks. Returns
        last-valid logits [B, 1, V_padded] and bumps the prefill
        counters."""
        batch = {"tokens": jnp.asarray(tokens)}
        batch.update(extra)
        bt = None if block_tables is None else jnp.asarray(block_tables)
        st = None if state_tables is None else jnp.asarray(state_tables)
        logits, self.caches = self._step(
            self.params, batch, self.caches, jnp.asarray(pos),
            jnp.asarray(active), jnp.asarray(n_valid), bt, st,
            n=self.n, binary=self.scfg.binary,
            page_topn=self.scfg.page_topn)
        self.close_exposed()
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += int(np.asarray(n_valid).sum())
        self.stats["prefill_rows"] += int(np.size(tokens))
        return logits

    def decode_step(self, tokens: np.ndarray, pos: np.ndarray,
                    active: np.ndarray,
                    block_tables: np.ndarray | None,
                    state_tables: np.ndarray | None = None) -> Array:
        """One batched ragged decode step; returns logits [B, 1, V_padded]."""
        bt = None if block_tables is None else jnp.asarray(block_tables)
        st = None if state_tables is None else jnp.asarray(state_tables)
        logits, self.caches = self._step(
            self.params,
            {"tokens": jnp.asarray(np.asarray(tokens, np.int32))[:, None]},
            self.caches, jnp.asarray(pos), jnp.asarray(active), None, bt, st,
            n=self.n, binary=self.scfg.binary,
            page_topn=self.scfg.page_topn)
        self.close_exposed()
        if self.scfg.paged:
            self._count_decode_traffic(pos, active)
        return logits

    def _count_decode_traffic(self, pos: np.ndarray,
                              active: np.ndarray) -> None:
        """Host-side pages-touched / HBM-byte accounting for one paged
        decode step (pure arithmetic on the plan's positions — no device
        round-trip, so the trace pin is untouched).

        `decode_pages_touched` counts pages whose V is read, summed over
        active slots (per layer-instance and kv-head the count is
        identical, so it is NOT multiplied out — it is the per-slot
        page-sparsity signal). `decode_hbm_bytes` is the estimated total
        K+V traffic across all attention layer instances and kv heads:
        dense reads every resident page's K and V; page-sparse phase 1
        reads every resident page's k_bits and phase 2 reads only the
        min(page_topn, resident) selected pages' k_bits + V.

        `decode_pages_listed` counts the block-table pages the decode
        kernel is handed (max_blocks per slot, or min(page_topn,
        max_blocks) per compacted row), `decode_pages_walked` those it
        walks: the ceil((pos+1)/page) resident ones, for every slot,
        active or not, as the kernel sees length pos + 1 for each.
        """
        ptn = self.scfg.page_topn
        nb = pages_needed(self.scfg.max_len, self.page)
        listed = nb if ptn is None else min(ptn, nb)
        pos = np.asarray(pos, np.int64)
        walked = np.minimum((pos + self.page) // self.page, listed)
        self.stats["decode_pages_walked"] += int(walked.sum())
        self.stats["decode_pages_listed"] += listed * walked.size
        res = (pos[np.asarray(active, bool)]
               + self.page) // self.page          # ceil((pos+1)/page)
        sel = res if ptn is None else np.minimum(res, ptn)
        self.stats["decode_pages_touched"] += int(sel.sum())
        kb, vb = self._page_k_bytes, self._page_v_bytes
        if ptn is None:
            step_bytes = int((res * (kb + vb)).sum())
        else:
            step_bytes = int((res * kb + sel * (kb + vb)).sum())
        self.stats["decode_hbm_bytes"] += step_bytes * self._attn_rows

    # ------------------------------------------------------------------
    # plan execution
    # ------------------------------------------------------------------
    def execute_async(self, plan: SchedulePlan) -> _PendingStep:
        """Dispatch one SchedulePlan without the final host sync: swap
        transfers, state ops, prefill chunks (whose completion samples are
        drawn eagerly — the same-step decode handoff feeds on them) and
        the batched decode launch all go to the device, but the decode
        logits are NOT materialized. The returned `_PendingStep` is
        redeemed by `wait()`; between the two the caller's host thread is
        free — that window is where the pipelined engine builds plan
        N+1."""
        results: dict[int, list[int]] = collections.defaultdict(list)
        for swap_in in plan.swap_ins:               # 1. restores
            with _span("serve.swap", pages=len(swap_in.pages)):
                self._swap_in_pages(swap_in.request_id, swap_in.pages,
                                    swap_in.state_page)
        for rc in plan.reclaims:                    # 2. gathers
            if rc.kind == "swap-out":
                with _span("serve.swap", pages=len(rc.pages)):
                    self._swap_out_pages(rc.request_id, rc.pages,
                                         rc.state_page)
        for adm in plan.admissions:                 # 3. state entry init
            if adm.state_page < 0 or adm.resume == "swap":
                continue
            if adm.state_restore >= 0:
                self._state_copy(adm.state_restore, adm.state_page,
                                 count=False)
            else:
                self._state_zero(adm.state_page)
        b = self.scfg.batch_slots
        vocab = self.cfg.vocab_size
        sampled: dict[int, int] = {}
        eos_hit: set[int] = set()
        for ch in plan.prefill:                     # 4. prefill chunks
            req = ch.request
            s = int(req.tokens.size)
            nv = ch.hi - ch.lo
            tokens = np.zeros((b, self.chunk), np.int32)
            tokens[ch.slot, :nv] = req.tokens[ch.lo:ch.hi]
            active = np.zeros((b,), bool)
            active[ch.slot] = True
            n_valid = np.zeros((b,), np.int32)
            n_valid[ch.slot] = nv
            with _span("serve.prefill_chunk", request_id=req.request_id,
                       lo=ch.lo, hi=ch.hi):
                logits = self.prefill_step(
                    tokens,
                    _chunk_extra(req.extra, s, ch.lo, ch.hi, self.chunk,
                                 batch=b, row=ch.slot),
                    np.asarray(ch.pos, np.int32), active, n_valid,
                    plan.block_tables, plan.state_tables)
                if self.telemetry is not None:
                    self.telemetry.on_chunk(req.request_id)
                if ch.state_ckpt >= 0:
                    # checkpoint the recurrent state at this chunk's
                    # page-aligned frontier for later prefix restores
                    self._state_copy(int(plan.state_tables[ch.slot]),
                                     ch.state_ckpt)
                if ch.samples:
                    tok, = self._sample_rows(logits[ch.slot, 0, :vocab],
                                             [(0, req, req.sampling, ch.rng)])
                    sampled[ch.slot] = tok
                    results[ch.slot].append(tok)
                    if ch.eos_token is not None and tok == ch.eos_token:
                        eos_hit.add(ch.slot)
        entries = [e for e in plan.decode if e.slot not in eos_hit]
        logits = None
        if entries:                                 # 5. batched decode
            tokens = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            for e in entries:
                tokens[e.slot] = (sampled[e.slot] if e.token is None
                                  else e.token)
                active[e.slot] = True
            with _span("serve.decode", slots=len(entries)):
                logits = self.decode_step(
                    tokens, np.asarray(plan.decode_pos, np.int32), active,
                    plan.block_tables, plan.state_tables)
            self.stats["decode_steps"] += 1
        return _PendingStep(results=dict(results), entries=entries,
                            logits=logits)

    def wait(self, pending: _PendingStep) -> dict[int, list[int]]:
        """The host sync for one dispatched step: land pending swap-out
        bytes, materialize the decode logits, and draw the decode tokens
        (in plan entry order — the rng stream is identical to the fully
        synchronous path)."""
        self._finalize_swaps()
        if pending.logits is not None:
            toks = self._sample_rows(
                pending.logits[:, 0, :self.cfg.vocab_size],
                [(e.slot, e.request, e.sampling, e.rng)
                 for e in pending.entries])
            for e, tok in zip(pending.entries, toks):
                pending.results.setdefault(e.slot, []).append(tok)
            pending.logits = None
        return pending.results

    # ------------------------------------------------------------------
    # page swap transfers (the data half of swap-out preemption)
    # ------------------------------------------------------------------
    def _pool_keys(self):
        for i, ch in enumerate(self.cfg.layer_pattern):
            if ch == "A":
                yield f"pos{i}"

    def _state_keys(self):
        for i in self._state_positions:
            yield f"pos{i}"

    def _swap_out_pages(self, request_id: int, pages: tuple,
                        state_page: int = -1) -> None:
        """Gather a victim's device pages (every paged leaf: packed k_bits
        + v, or the fp k/v twins) — plus, for hybrid models, its pooled
        state entry — one indexed take per leaf, page granularity. The
        take is an on-device copy dispatched BEFORE any planned write can
        recycle the pages (functional arrays: it snapshots the pre-recycle
        contents by construction), and the D2H transfer is started
        asynchronously — host bytes land at the next `wait()`/`sync()`
        instead of blocking dispatch here."""
        idx = jnp.asarray(np.asarray(pages, np.int32))
        kv: dict[str, dict[str, Any]] = {}
        nbytes = 0
        for key in self._pool_keys():
            taken = {}
            for name, leaf in self.caches[key].items():
                arr = leaf[:, idx]                  # [n_groups, k, ...]
                if hasattr(arr, "copy_to_host_async"):
                    arr.copy_to_host_async()
                taken[name] = arr
                nbytes += arr.nbytes
            kv[key] = taken
        state: dict[str, dict[str, Any]] = {}
        if state_page >= 0:
            for key in self._state_keys():
                taken = {}
                for name, leaf in self.caches[key].items():
                    arr = leaf[:, state_page]       # [n_groups, ...]
                    if hasattr(arr, "copy_to_host_async"):
                        arr.copy_to_host_async()
                    taken[name] = arr
                    nbytes += arr.nbytes
                state[key] = taken
        self._swap_store[request_id] = {"kv": kv, "state": state}
        self._pending_swaps.append(request_id)
        self.stats["swap_out_bytes"] += nbytes
        if self.telemetry is not None:
            self.telemetry.on_swap_bytes(request_id, out=nbytes)

    def _finalize_swaps(self) -> None:
        """Convert pending swap-out gathers to host numpy — the blocking
        half of the async D2H, deferred to the step's sync point so the
        transfer overlaps the decode it was dispatched with."""
        for rid in self._pending_swaps:
            payload = self._swap_store.get(rid)
            if payload is None:
                continue               # cancelled or already restored
            for part in ("kv", "state"):
                for key, taken in payload[part].items():
                    payload[part][key] = {name: np.asarray(arr)
                                          for name, arr in taken.items()}
        self._pending_swaps.clear()

    def _swap_in_pages(self, request_id: int, pages: tuple,
                       state_page: int = -1) -> None:
        """Scatter a swapped request's stored page contents (and state
        entry) into its freshly allocated device pages — the exact inverse
        of the swap-out gather, restoring the KV and recurrent state
        verbatim (bit-identical resume, zero re-prefill)."""
        payload = self._swap_store.pop(request_id)
        idx = jnp.asarray(np.asarray(pages, np.int32))
        nbytes = 0
        caches = dict(self.caches)
        for key, stored in payload["kv"].items():
            layer = dict(caches[key])
            for name, arr in stored.items():
                layer[name] = layer[name].at[:, idx].set(jnp.asarray(arr))
                nbytes += arr.nbytes
            caches[key] = layer
        for key, stored in payload["state"].items():
            layer = dict(caches[key])
            for name, arr in stored.items():
                layer[name] = layer[name].at[:, state_page].set(
                    jnp.asarray(arr))
                nbytes += arr.nbytes
            caches[key] = layer
        self.caches = caches
        self.stats["swap_in_bytes"] += nbytes
        if self.telemetry is not None:
            self.telemetry.on_swap_bytes(request_id, in_=nbytes)

    # ------------------------------------------------------------------
    # pooled state entry ops (eager, outside the jitted step)
    # ------------------------------------------------------------------
    def _state_zero(self, entry: int) -> None:
        """Zero one pooled state entry across every state-carrying layer
        (fresh/recompute admissions must never inherit the previous
        occupant's h/conv/cross state)."""
        caches = dict(self.caches)
        for key in self._state_keys():
            caches[key] = {
                name: leaf.at[:, entry].set(jnp.zeros((), leaf.dtype))
                for name, leaf in caches[key].items()}
        self.caches = caches

    def _state_copy(self, src: int, dst: int, count: bool = True) -> None:
        """Copy pooled state entry src -> dst (checkpoint capture when
        `count`, checkpoint restore otherwise — restores are counted by
        the scheduler, capture bytes by us)."""
        nbytes = 0
        caches = dict(self.caches)
        for key in self._state_keys():
            layer = {}
            for name, leaf in caches[key].items():
                layer[name] = leaf.at[:, dst].set(leaf[:, src])
                nbytes += (leaf.size // leaf.shape[1]) * leaf.dtype.itemsize
            caches[key] = layer
        self.caches = caches
        if count:
            self.stats["state_ckpt_bytes"] += nbytes
