"""Serving benchmark: throughput AND tail latency of the scheduler.

Interleaved chunked prefill is a *tail-latency* feature — tokens/s cannot
see it. So besides the tokens/s slot sweep this harness drives staggered
mixed-length arrivals and reports per-request TTFT (submit -> first token)
and inter-token latency (ITL) percentiles p50/p95/p99. A resident slot's
ITL during a concurrent admission is bounded by one prefill chunk instead
of a whole prompt.

CPU numbers are correctness-grade (interpret-mode kernel / jnp reference
path), but the relative trends — slot scaling, ragged admission cost, and
the chunk-budget/ITL trade — are real on any backend.

Every engine runs with a telemetry hub attached: TTFT/ITL/queue-time
samples and preemption attribution are derived from the drained
per-request ``RequestMetrics`` (``Engine.pop_finished_metrics()``), each
case ends with the ``Engine.check()`` invariant probe, ``--trace-file``
dumps the step flight recorder as schema-validated JSONL after every
driven workload, and ``--metrics`` renders the Prometheus-text registry.

CSV contract: throughput rows keep ``serve_<case>,us_per_token,tok_per_s``;
latency rows are ``serve_<case>_{ttft|itl|queue}_p{50|95|99},<ms>,ms``,
preemption-attribution rows are ``serve_<case>_preempt,<victims>,...``
(per-kind reclaim totals, asserted equal to the scheduler's aggregate
``preemptions`` counter), and one
``serve_<case>_stats,<prefill_chunks>,<decode_steps>`` row per timed case
(the engine's counters are reset after warm-up, so a jump in chunk or
step counts flags a scheduling/trace regression). With ``--paged`` every
case additionally emits a KV-pool row
``serve_<case>_kvpool,<pages_in_use>,<peak_pages>,<preemptions>,
<max_residents>`` and the harness runs an *overcommit* case whose page
pool holds fewer tokens than ``batch_slots x max_len`` — dense layout
capacity — while still serving the whole workload (preempting on
exhaustion), i.e. paging admits strictly more concurrent residents than
the dense cache could hold.

With ``--prefix-cache`` a *shared-system-prompt* case runs the same
staggered arrival workload twice — cold (plain paged) and with automatic
prefix caching — and reports the TTFT percentiles and ``prefill_tokens``
side by side plus the hit-rate / cached-page columns
(``serve_prefix_on_cached,<cached_tokens>,<hit_rate>`` and
``serve_prefix_on_pages,<page_hits>,<registered>,<evictions>``): the
matched prefix's prefill chunks are skipped outright, so shared-prefix
TTFT drops from O(prompt) to O(suffix).

With ``--swap-pages N`` a *preemption-mechanism* case runs the overcommit
workload twice — recompute preemption (swap off) vs page-aligned swap-out
to an N-page host pool — and reports TTFT/ITL percentiles side by side
plus the preemption-cost columns
(``serve_swapout_{off,on}_tokens,<swapped_back>,<re_prefilled>`` and
``serve_swapout_on_bytes,<swap_out_bytes>,<swap_in_bytes>``): swapped
victims restore their pages verbatim instead of replaying their prompt +
generation, so the harness asserts the swap pass re-prefills strictly
fewer tokens.

With ``--hybrid`` the shared-system-prompt workload additionally runs on
a reduced ``mamba2-130m`` (pure-SSM) model served through the pooled
recurrent state: cold vs prefix-cached passes emit the state-pool columns
(``serve_hybrid_{off,on}_s<N>_statepool,<in_use>,<peak_held>,<ckpts>``,
``..._on_s<N>_state,<state_restores>,<state_ckpt_bytes>`` and
``..._on_s<N>_cached,<cached_tokens>,<hit_rate>``); the harness asserts
the warm pass restores recurrent-state checkpoints and does strictly
less prefill work than cold. With ``--swap-pages`` it also runs an
overcommitted hybrid pass whose victims carry their state entry through
the host swap pool (``serve_hybrid_swap_s<N>,<swap_outs>,<bytes>``).

With ``--async`` two pipelined-front-end cases run. The *double-buffer*
case drives the overcommitted staggered workload through
``Engine.step_pipelined()`` — plan N+1 is built on the host while step N
runs on the device — side by side with the sync loop, and reports the
fraction of scheduling work hidden inside the device window
(``serve_async_pipe_s<N>_overlap,<frac>,<steps>``; asserted > 0.5 on the
default workload, > 0 under ``--smoke``). The *open-loop* case submits
Poisson arrivals through the asyncio front end (``AsyncEngine``) at
0.5x/1x/2x the measured closed-loop capacity — arrivals keep coming
regardless of completions, the regime where queueing delay compounds —
and reports goodput under SLO: the attainment fraction at self-calibrated
TTFT/ITL deadlines and the SLO-attaining request rate per offered QPS
(``serve_openloop_<m>x_{offered|goodput}`` rows). The arrival process is
seeded by ``--seed``, stamped in the ``serve_openloop_meta`` row;
closed-loop rows are unaffected by the seed.
"""
from __future__ import annotations

import asyncio
import time

import jax
import numpy as np

from benchmarks.common import (causal_cfg, latency_samples, percentiles_ms,
                               preemption_attribution, scaling_efficiency,
                               slo_attainment)
from repro.launch.roofline import MODELED_KIND, chip_peaks
from repro.models import model as M
from repro.serve import AsyncEngine, Engine, ServeConfig, Telemetry

PROMPT_MEAN = 96
GEN = 16
MAX_LEN = 256
CHUNK = 64       # step() prefill token budget

# set by __main__: the trace file handed to every engine's telemetry hub
# (--trace-file) and the last hub built (--metrics renders its registry)
TELEMETRY = {"trace_file": None, "last": None}


def _prompts(n_req: int, skew: str, rng) -> list[np.ndarray]:
    if skew == "uniform":
        lens = [PROMPT_MEAN] * n_req
    else:  # mixed: 4x spread around the mean
        lo, hi = PROMPT_MEAN // 2, PROMPT_MEAN * 2
        lens = rng.integers(lo, hi, size=n_req).tolist()
    return [rng.integers(0, 512, size=int(s)) for s in lens]


def _drive(eng: Engine, prompts: list[np.ndarray], *, stagger: int = 0,
           pipelined: bool = False) -> dict:
    """Run the workload; latency samples come from the engine's telemetry
    layer (per-request RequestMetrics) instead of ad-hoc bookkeeping.

    stagger > 0 trickles one request in every `stagger` scheduler steps
    after the first slot-filling wave (staggered arrivals — the TTFT/ITL
    measurement regime); 0 submits everything up front (throughput).
    pipelined drives the double-buffered `step_pipelined()` loop instead
    of the sync `step()` (the loop also waits out the final in-flight
    device step). Returns {"wall": s, "ttft": [s], "itl": [s],
    "queue": [s], "gen": n_tokens, "metrics": [RequestMetrics]}.
    """
    step = eng.step_pipelined if pipelined else eng.step
    t0 = time.perf_counter()
    n_first = len(prompts) if not stagger else min(eng.scfg.batch_slots,
                                                   len(prompts))
    for p in prompts[:n_first]:
        eng.submit(p, max_new_tokens=GEN)
    nxt, steps = n_first, 0
    metrics = []
    while (eng.queue or any(s.request is not None for s in eng.slots)
           or nxt < len(prompts)
           or (pipelined and eng._inflight is not None)):
        step()
        metrics += eng.pop_finished_metrics()
        steps += 1
        if stagger and nxt < len(prompts) and steps % stagger == 0:
            eng.submit(prompts[nxt], max_new_tokens=GEN)
            nxt += 1
    wall = time.perf_counter() - t0
    metrics += eng.pop_finished_metrics()
    if stagger:
        # the latency regime exists to measure admissions into a BUSY
        # batch; if nothing trickled in mid-flight the numbers are lies
        assert nxt > n_first, "staggered regime never fired: need " \
                              "more requests than slots"
    eng.check()          # pool/slot invariants must hold after every case
    if eng.telemetry is not None and eng.telemetry.trace_file:
        eng.dump_trace(requests=metrics)
    lat = latency_samples(metrics)
    return {"wall": wall, "ttft": lat["ttft"], "itl": lat["itl"],
            "queue": lat["queue"],
            "gen": sum(m.n_generated for m in metrics), "metrics": metrics}


def _engine(params, cfg, *, slots: int, binary: bool, paged: bool = False,
            page_size: int = 16, n_pages: int | None = None,
            prefix_cache: bool = False, swap_pages: int = 0,
            page_topn: int | None = None, mesh=None) -> Engine:
    tel = Telemetry(trace_file=TELEMETRY["trace_file"])
    TELEMETRY["last"] = tel
    return Engine(cfg, params, ServeConfig(max_len=MAX_LEN, batch_slots=slots,
                                           binary=binary,
                                           prefill_chunk=CHUNK, paged=paged,
                                           page_size=page_size,
                                           n_pages=n_pages,
                                           prefix_cache=prefix_cache,
                                           swap_pages=swap_pages,
                                           page_topn=page_topn,
                                           mesh=mesh),
                  telemetry=tel)


def _kvpool_row(name: str, eng: Engine) -> str:
    """KV-pool columns: pages in use, peak watermark, preemption count,
    max concurrent residents, then the pool's per-device and total cache
    bytes (equal on one device; under --mesh-model the per-device column
    must show the 1/N head-sharded split). Sampled after the workload
    drains, so pages-in-use doubles as a leak check — any nonzero value
    means a finished/preempted request failed to return pages (assert
    here rather than letting the CSV silently absorb it)."""
    alloc = eng.allocator
    assert alloc.in_use == 0, (
        f"{alloc.in_use} pages leaked after the workload drained")
    total_b, per_b = eng.runner.cache_device_bytes()
    return (f"{name}_kvpool,{alloc.in_use},{alloc.peak_in_use},"
            f"{eng.stats['preemptions']},{eng.stats['max_residents']},"
            f"{per_b},{total_b}")


def _serve_case(params, cfg, *, slots: int, skew: str, binary: bool,
                n_req: int, stagger: int = 0, seed: int = 0,
                paged: bool = False, page_size: int = 16,
                n_pages: int | None = None) -> dict:
    rng = np.random.default_rng(seed)
    eng = _engine(params, cfg, slots=slots, binary=binary, paged=paged,
                  page_size=page_size, n_pages=n_pages)
    prompts = _prompts(n_req, skew, rng)
    # warm-up: run the identical workload once so the (chunk-length-
    # agnostic) prefill trace and the decode trace compile outside the
    # timed region — then RESET the counters so eng.stats reflects only
    # the timed pass (the old harness double-counted the warm-up)
    _drive(eng, prompts, stagger=stagger)
    eng.reset_stats()
    out = _drive(eng, prompts, stagger=stagger)
    out["stats"] = dict(eng.stats)
    out["engine"] = eng
    return out


def run(print_fn=print, slot_counts=(1, 2, 4), n_req: int = 4,
        stagger: int = 2, paged: bool = False,
        page_size: int = 16, prefix_cache: bool = False,
        swap_pages: int = 0, page_topn: int | None = None,
        hybrid: bool = False, async_mode: bool = False, seed: int = 0,
        mesh_model: int = 0, smoke: bool = False) -> list[str]:
    csv = []
    cfg = causal_cfg(d=64, layers=2, heads=4)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mode = f", paged (page {page_size})" if paged else ""
    print_fn(f"serving: prompts~{PROMPT_MEAN}, gen {GEN}, {n_req} requests, "
             f"prefill budget {CHUNK} tok/step{mode}")
    # environment stamp: device count / backend / mesh shape, so scaling
    # rows (and every other row) are self-describing in aggregated CSVs
    mesh_shape = f"1x{mesh_model}" if mesh_model > 1 else "1x1"
    csv.append(f"serve_env_meta,{len(jax.devices())},"
               f"{jax.default_backend()},mesh={mesh_shape}")
    prefix = "serve_paged" if paged else "serve"
    for binary in (True, False):
        tag = "binary" if binary else "baseline"
        for slots in slot_counts:
            r = _serve_case(params, cfg, slots=slots, skew="uniform",
                            binary=binary, n_req=n_req, paged=paged,
                            page_size=page_size)
            us, tps = r["wall"] / r["gen"] * 1e6, r["gen"] / r["wall"]
            print_fn(f"  {tag:8s} slots={slots} uniform: "
                     f"{tps:7.1f} tok/s ({us:.0f} us/tok)")
            csv.append(f"{prefix}_{tag}_s{slots}_uniform,{us:.1f},{tps:.2f}")
            if paged:
                csv.append(_kvpool_row(f"{prefix}_{tag}_s{slots}_uniform",
                                       r["engine"]))
        # staggered mixed-length arrivals: the latency-percentile case.
        # More requests than slots, so later arrivals are admitted while
        # residents decode — the regime interleaved prefill exists for.
        slots = slot_counts[-1]
        n_lat = max(n_req, slots + 2)
        r = _serve_case(params, cfg, slots=slots, skew="mixed",
                        binary=binary, n_req=n_lat, stagger=stagger,
                        paged=paged, page_size=page_size)
        us, tps = r["wall"] / r["gen"] * 1e6, r["gen"] / r["wall"]
        name = f"{prefix}_{tag}_s{slots}_mixed"
        csv.append(f"{name},{us:.1f},{tps:.2f}")
        t50, t95, t99 = percentiles_ms(r["ttft"])
        i50, i95, i99 = percentiles_ms(r["itl"])
        q50, q95, q99 = percentiles_ms(r["queue"])
        print_fn(f"  {tag:8s} slots={slots} mixed+staggered: "
                 f"{tps:7.1f} tok/s | TTFT p50/p95/p99 "
                 f"{t50:.1f}/{t95:.1f}/{t99:.1f} ms | ITL "
                 f"{i50:.1f}/{i95:.1f}/{i99:.1f} ms | queue "
                 f"{q50:.1f}/{q95:.1f}/{q99:.1f} ms")
        for metric, (p50, p95, p99) in (("ttft", (t50, t95, t99)),
                                        ("itl", (i50, i95, i99)),
                                        ("queue", (q50, q95, q99))):
            csv.append(f"{name}_{metric}_p50,{p50:.2f},ms")
            csv.append(f"{name}_{metric}_p95,{p95:.2f},ms")
            csv.append(f"{name}_{metric}_p99,{p99:.2f},ms")
        st = r["stats"]
        print_fn(f"  {tag:8s} stats (timed pass only): {st}")
        csv.append(f"{name}_stats,{st['prefill_chunks']},{st['decode_steps']}")
        if paged:
            csv.append(_kvpool_row(name, r["engine"]))
    if paged:
        csv += _overcommit_case(print_fn, params, cfg,
                                slots=slot_counts[-1], n_req=n_req,
                                page_size=page_size)
    if prefix_cache:
        csv += _prefix_case(print_fn, params, cfg, slots=slot_counts[-1],
                            n_req=n_req, stagger=stagger,
                            page_size=page_size)
    if swap_pages:
        csv += _swap_case(print_fn, params, cfg, slots=slot_counts[-1],
                          n_req=n_req, stagger=stagger,
                          page_size=page_size, swap_pages=swap_pages)
    if page_topn:
        csv += _page_sparse_case(print_fn, params, cfg,
                                 slots=slot_counts[-1], n_req=n_req,
                                 page_size=page_size, page_topn=page_topn)
    if hybrid:
        csv += _hybrid_case(print_fn, slots=slot_counts[-1], n_req=n_req,
                            stagger=stagger, page_size=page_size,
                            swap_pages=swap_pages)
    if async_mode:
        csv += _async_case(print_fn, params, cfg, slots=slot_counts[-1],
                           n_req=n_req, stagger=stagger,
                           page_size=page_size, prefix_cache=prefix_cache,
                           swap_pages=swap_pages, smoke=smoke)
        csv += _openloop_case(print_fn, params, cfg, slots=slot_counts[-1],
                              page_size=page_size, seed=seed, smoke=smoke)
    if mesh_model > 1:
        csv += _mesh_case(print_fn, params, cfg, slots=slot_counts[-1],
                          n_req=n_req, page_size=page_size,
                          mesh_model=mesh_model)
    return csv


def _mesh_case(print_fn, params, cfg, *, slots: int, n_req: int,
               page_size: int, mesh_model: int) -> list[str]:
    """Tensor-parallel scaling sweep: the same paged binary workload at
    mesh model-axis sizes 1, 2, 4, ... up to --mesh-model.

    The acceptance criteria live in the harness, not in eyeballs:

    * sharded tokens are bit-identical to the single-device run;
    * the aggregate decode-HBM traffic model is mesh-independent (the
      logical work does not change), so per-device traffic is exactly
      aggregate/N;
    * each device holds exactly 1/N of the KV-pool bytes (kv-head
      sharding, divisibility validated);
    * modeled bandwidth-bound decode throughput — generated tokens over
      (per-device traffic / the modeled chip's HBM bandwidth) — increases
      monotonically with N, with scaling_efficiency reported per size.

    Wall-clock tok/s is reported but NOT asserted: forced host devices
    all live on one CPU, so the throughput model is exact arithmetic over
    measured traffic at the modeled chip's published HBM bandwidth.
    """
    from repro.launch.mesh import make_host_mesh
    hbm_bw = chip_peaks(MODELED_KIND).hbm_bw
    sweep = [m for m in (1, 2, 4, 8) if m <= mesh_model]
    if mesh_model not in sweep:
        sweep.append(mesh_model)
    rng = np.random.default_rng(7)
    prompts = _prompts(max(n_req, slots + 2), "mixed", rng)
    print_fn(f"  mesh sweep {sweep} over {len(jax.devices())} "
             f"{jax.default_backend()} device(s), kv_heads="
             f"{cfg.n_kv_heads}")
    rows: list[str] = []
    base_tokens = base_traffic = base_total = base_modeled = None
    prev_modeled = 0.0
    for m in sweep:
        mesh = make_host_mesh(data=1, model=m) if m > 1 else None
        eng = _engine(params, cfg, slots=slots, binary=True, paged=True,
                      page_size=page_size, mesh=mesh)
        _drive(eng, prompts, stagger=0)      # compile outside the timing
        eng.reset_stats()
        gen: dict[int, list[int]] = {}
        t0 = time.perf_counter()
        for p in prompts:
            gen[eng.submit(p, max_new_tokens=GEN)] = []
        while eng.queue or any(s.request is not None for s in eng.slots):
            for fr in eng.step():
                gen[fr.request_id] = [int(t) for t in fr.tokens]
        wall = time.perf_counter() - t0
        eng.check()
        tokens = [gen[rid] for rid in sorted(gen)]
        ngen = sum(len(t) for t in tokens)
        traffic = int(eng.stats["decode_hbm_bytes"])
        total_b, per_b = eng.runner.cache_device_bytes()
        assert per_b * m == total_b, (
            f"m={m}: per-device pool bytes {per_b} x {m} != {total_b} — "
            f"kv-head sharding is not an exact 1/N split")
        modeled = ngen / ((traffic / m) / hbm_bw)
        if base_tokens is None:
            base_tokens, base_traffic = tokens, traffic
            base_total, base_modeled = total_b, modeled
        else:
            assert tokens == base_tokens, (
                f"m={m}: sharded tokens diverge from single-device")
            assert traffic == base_traffic, (
                f"m={m}: aggregate HBM traffic model changed "
                f"({traffic} != {base_traffic})")
            assert total_b == base_total, (
                f"m={m}: logical pool bytes changed")
        assert modeled > prev_modeled, (
            f"m={m}: modeled decode throughput not monotonic "
            f"({modeled:.0f} <= {prev_modeled:.0f})")
        prev_modeled = modeled
        eff = scaling_efficiency(base_modeled, modeled, m)
        us, tps = wall / ngen * 1e6, ngen / wall
        print_fn(f"  mesh m={m}: {tps:7.1f} tok/s wall | modeled "
                 f"{modeled / 1e6:8.1f} Mtok/s (eff {eff:.2f}) | pool "
                 f"{per_b}/{total_b} B per-device/total | decode traffic "
                 f"{traffic} B aggregate")
        rows.append(f"serve_mesh_m{m},{us:.1f},{tps:.2f}")
        rows.append(f"serve_mesh_m{m}_model,{modeled:.1f},{eff:.3f}")
        rows.append(f"serve_mesh_m{m}_hbm,{per_b},{total_b}")
        rows.append(_kvpool_row(f"serve_mesh_m{m}", eng))
    return rows


def _async_case(print_fn, params, cfg, *, slots: int, n_req: int,
                stagger: int, page_size: int, prefix_cache: bool,
                swap_pages: int, smoke: bool) -> list[str]:
    """Double-buffered serving: the overcommitted staggered workload
    driven through `step_pipelined()` — the scheduler builds plan N+1
    (and commits step N's structural effects) while step N's device work
    is still in flight, syncing step N's sampled tokens only when plan
    N+1 is ready to launch. Bit-identical outputs vs the sync loop are
    pinned in tests/test_async_engine.py (including prefix-cache and
    swap interplay); here the harness measures what the overlap buys —
    the fraction of host scheduling work hidden inside the device window
    (from the flight recorder's per-step overlap timings) — and reports
    tok/s side by side with the sync loop on the same workload."""
    from repro.serve import pages_needed
    dense_pages = slots * pages_needed(MAX_LEN, page_size)
    n_pages = max(pages_needed(MAX_LEN, page_size), int(dense_pages * 0.4))
    rng = np.random.default_rng(19)
    prompts = _prompts(max(n_req, slots + 2), "mixed", rng)
    csv = []
    for pipelined in (False, True):
        tag = "pipe" if pipelined else "sync"
        eng = _engine(params, cfg, slots=slots, binary=True, paged=True,
                      page_size=page_size, n_pages=n_pages,
                      prefix_cache=prefix_cache, swap_pages=swap_pages)
        _drive(eng, prompts, stagger=stagger, pipelined=pipelined)
        eng.reset_stats()
        r = _drive(eng, prompts, stagger=stagger, pipelined=pipelined)
        tps = r["gen"] / r["wall"]
        name = f"serve_async_{tag}_s{slots}"
        csv.append(f"{name},{r['wall'] / r['gen'] * 1e6:.1f},{tps:.2f}")
        if pipelined:
            ov = eng.overlap_stats()
            assert ov["pipelined_steps"] > 0, dict(eng.stats)
            # the default overcommit workload must hide most of its
            # scheduling inside the device window; the smoke workload is
            # too small to promise a ratio, only that overlap happened
            floor = 0.0 if smoke else 0.5
            assert ov["overlap_frac"] > floor, ov
            csv.append(f"{name}_overlap,{ov['overlap_frac']:.3f},"
                       f"{ov['pipelined_steps']}")
            print_fn(f"  async    slots={slots} double-buffer: {tps:7.1f} "
                     f"tok/s | {100 * ov['overlap_frac']:.0f}% of "
                     f"scheduling overlapped across "
                     f"{ov['pipelined_steps']} pipelined steps")
        else:
            print_fn(f"  async    slots={slots} sync loop:     "
                     f"{tps:7.1f} tok/s")
    return csv


def _openloop_pass(eng: Engine, prompts: list[np.ndarray],
                   arrive_s: np.ndarray) -> tuple[float, list]:
    """One open-loop pass: clients submit through the asyncio front end
    at fixed absolute arrival offsets (seconds from pass start),
    regardless of completions, while `AsyncEngine.run()` drives the
    pipelined loop in a worker thread. Returns (wall_s, metrics)."""
    aeng = AsyncEngine(eng)

    async def client(i: int):
        await asyncio.sleep(float(arrive_s[i]))
        h = await aeng.submit(prompts[i], max_new_tokens=GEN)
        await h.result()

    async def main():
        runner = asyncio.ensure_future(aeng.run())
        t0 = time.perf_counter()
        await asyncio.gather(*[client(i) for i in range(len(prompts))])
        aeng.stop()
        await runner
        return time.perf_counter() - t0

    wall = asyncio.run(main())
    eng.check()
    if eng.telemetry is not None and eng.telemetry.trace_file:
        eng.dump_trace(requests=aeng.finished_metrics)
    return wall, list(aeng.finished_metrics)


def _openloop_case(print_fn, params, cfg, *, slots: int, page_size: int,
                   seed: int, smoke: bool) -> list[str]:
    """Open-loop goodput under SLO: Poisson arrivals at a fixed offered
    rate keep coming whether or not the engine keeps up — the serving
    regime where queueing delay compounds past saturation, which a
    closed-loop driver (submit-on-completion) structurally cannot
    produce. A closed-loop calibration pass sets the capacity estimate
    and the SLO deadlines (4x the uncongested p50 TTFT / ITL on this
    machine — CPU-absolute numbers are meaningless across hosts, the
    *shape* of attainment vs offered load is the result); the sweep then
    offers 0.5x/1x/2x capacity and reports attainment (fraction of
    requests meeting both deadlines, via `slo_attainment`) and goodput
    (SLO-attaining request rate). Arrivals are drawn from --seed,
    stamped in the meta row; closed-loop rows never see the seed."""
    rng = np.random.default_rng(seed)
    n_req = 6 if smoke else 16
    prompts = _prompts(n_req, "mixed", rng)

    eng = _engine(params, cfg, slots=slots, binary=True, paged=True,
                  page_size=page_size)
    _drive(eng, prompts, stagger=0, pipelined=True)      # compile warm-up
    eng.reset_stats()
    cal = _drive(eng, prompts, stagger=0, pipelined=True)
    cap_qps = len(prompts) / cal["wall"]
    t50, _, _ = percentiles_ms(cal["ttft"])
    i50, _, _ = percentiles_ms(cal["itl"])
    slo_ttft_s, slo_itl_s = 4 * t50 / 1e3, 4 * i50 / 1e3
    csv = [f"serve_openloop_meta,{seed},seed",
           f"serve_openloop_slo,{4 * t50:.2f},{4 * i50:.2f}"]
    print_fn(f"  open-loop slots={slots}: capacity ~{cap_qps:.2f} req/s, "
             f"SLO ttft<={4 * t50:.1f} ms itl<={4 * i50:.1f} ms "
             f"(seed {seed})")
    for mult in ((1.0,) if smoke else (0.5, 1.0, 2.0)):
        qps = cap_qps * mult
        arrive = np.cumsum(rng.exponential(1.0 / qps, size=n_req))
        eng = _engine(params, cfg, slots=slots, binary=True, paged=True,
                      page_size=page_size)
        _drive(eng, prompts[:2], stagger=0, pipelined=True)   # compile
        eng.reset_stats()
        wall, metrics = _openloop_pass(eng, prompts, arrive)
        assert len(metrics) == n_req, (len(metrics), n_req)
        att = slo_attainment(metrics, ttft_s=slo_ttft_s, itl_s=slo_itl_s)
        good = att["attained"] / wall
        tag = f"{mult:g}x"
        csv.append(f"serve_openloop_{tag}_offered,{qps:.2f},qps")
        csv.append(f"serve_openloop_{tag}_goodput,{good:.2f},"
                   f"{att['attainment']:.3f}")
        print_fn(f"  open-loop {tag:4s}: offered {qps:.2f} req/s -> "
                 f"{att['attained']}/{att['total']} in SLO "
                 f"({100 * att['attainment']:.0f}%), goodput "
                 f"{good:.2f} req/s")
    return csv


def _hybrid_case(print_fn, *, slots: int, n_req: int, stagger: int,
                 page_size: int, swap_pages: int) -> list[str]:
    """Stateful-model serving through the pooled recurrent state: the
    shared-system-prompt workload on a reduced mamba2-130m (pure-SSM)
    model, cold vs prefix-cached. A warm admission restores the state
    checkpoint captured at the matched page-aligned boundary, so the
    cached pass skips the shared prefix's prefill chunks AND its SSM
    recurrence (bit-identical outputs are pinned in
    tests/test_prefix_cache.py; the harness asserts the prefill-work
    reduction and the restore count). With swap space an overcommitted
    pass additionally swaps victims' state entries through the host
    pool alongside their KV pages."""
    from repro.configs import get_config
    from repro.serve import pages_needed
    cfg = get_config("mamba2-130m").reduced()
    params = M.init_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(23)
    sys_prompt = rng.integers(0, cfg.vocab_size, size=2 * PROMPT_MEAN)
    suffix = min(page_size, MAX_LEN - 2 * PROMPT_MEAN - GEN)
    assert suffix >= 1, "shared prompt leaves no room for a unique suffix"
    n_lat = max(n_req, slots + 2)
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(0, cfg.vocab_size, size=suffix)])
               for _ in range(n_lat)]
    csv, ptoks = [], {}
    for cached in (False, True):
        tag = "on" if cached else "off"
        eng = _engine(params, cfg, slots=slots, binary=True, paged=True,
                      page_size=page_size, prefix_cache=cached)
        _drive(eng, prompts, stagger=stagger)        # warm-up + index fill
        eng.reset_stats()
        r = _drive(eng, prompts, stagger=stagger)
        st = eng.stats
        name = f"serve_hybrid_{tag}_s{slots}"
        t50, _, _ = percentiles_ms(r["ttft"])
        csv.append(f"{name}_ttft_p50,{t50:.2f},ms")
        csv.append(f"{name}_prefill_tokens,{st['prefill_tokens']},tok")
        csv.append(_kvpool_row(name, eng))
        sp = eng.statepool
        assert sp is not None and sp.n_held == 0, (
            f"{sp.n_held} state entries leaked after the workload drained")
        csv.append(f"{name}_statepool,{sp.n_held},{sp.peak_held},{sp.n_ckpt}")
        ptoks[tag] = st["prefill_tokens"]
        if cached:
            seen = st["cached_tokens"] + st["prefill_tokens"]
            rate = st["cached_tokens"] / max(seen, 1)
            csv.append(f"{name}_cached,{st['cached_tokens']},{rate:.3f}")
            csv.append(f"{name}_state,{st['state_restores']},"
                       f"{st['state_ckpt_bytes']}")
            assert st["state_restores"] > 0, (
                "warm hybrid pass never restored a state checkpoint",
                dict(st))
            print_fn(f"  hybrid   slots={slots} shared-prompt cached: TTFT "
                     f"p50 {t50:.1f} ms, prefill {st['prefill_tokens']} tok, "
                     f"{st['cached_tokens']} cached ({100 * rate:.0f}%), "
                     f"{st['state_restores']} state restores, "
                     f"{st['state_ckpt_bytes']} ckpt B "
                     f"(pool peak {sp.peak_held} held / {sp.n_ckpt} ckpts)")
        else:
            print_fn(f"  hybrid   slots={slots} shared-prompt cold:   TTFT "
                     f"p50 {t50:.1f} ms, prefill {st['prefill_tokens']} tok")
    assert ptoks["on"] < ptoks["off"], (
        "warm hybrid pass failed to reduce prefill work", ptoks)
    if swap_pages:
        dense_pages = slots * pages_needed(MAX_LEN, page_size)
        n_pages = max(pages_needed(MAX_LEN, page_size),
                      int(dense_pages * 0.4))
        # mixed-length prompts short enough for residents to CO-reside
        # until decode growth forces the eviction — a decode-phase victim
        # is what swap-out exists for (the long shared prompt above can't
        # fit two residents in the overcommitted pool at all, so every
        # eviction there would be an admission-time self-preempt)
        lens = rng.integers(PROMPT_MEAN // 2, 2 * PROMPT_MEAN,
                            size=max(n_req, slots + 2))
        sw_prompts = [rng.integers(0, cfg.vocab_size, size=int(s))
                      for s in lens]
        eng = _engine(params, cfg, slots=slots, binary=True, paged=True,
                      page_size=page_size, n_pages=n_pages,
                      swap_pages=swap_pages)
        _drive(eng, sw_prompts, stagger=stagger)
        eng.reset_stats()
        _drive(eng, sw_prompts, stagger=stagger)
        st = eng.stats
        assert st["swap_outs"] > 0, (
            "hybrid overcommit never forced a swap-out", dict(st))
        assert eng.statepool.n_held == 0, "state entries leaked over swap"
        csv.append(f"serve_hybrid_swap_s{slots},{st['swap_outs']},"
                   f"{st['swap_out_bytes']}")
        print_fn(f"  hybrid   slots={slots} overcommit+swap: "
                 f"{st['swap_outs']} state+KV swap-outs, "
                 f"{st['swap_out_bytes']} B out")
    return csv


def _page_sparse_case(print_fn, params, cfg, *, slots: int, n_req: int,
                      page_size: int, page_topn: int) -> list[str]:
    """Two-phase top-N page-sparse decode vs dense paged decode: the same
    workload runs with every resident page attended and with only the
    `page_topn` best-scoring pages (plus the frontier page) per decode
    step. Reports the host-side decode traffic counters
    (``decode_pages_touched`` / ``decode_hbm_bytes`` — phase-1 scoring
    reads every resident page's k_bits, phase-2 attends only the selected
    pages' K+V) and the generation quality delta (fraction of dense-run
    tokens reproduced). Exact-parity at page_topn >= resident pages is
    pinned in tests/test_serve_ragged.py; here the harness asserts the
    sparse pass touches strictly fewer decode pages than dense."""
    rng = np.random.default_rng(17)
    prompts = _prompts(max(n_req, 2), "mixed", rng)
    csv, toks, traffic = [], {}, {}
    for ptn in (None, page_topn):
        tag = "dense" if ptn is None else f"topn{ptn}"
        eng = _engine(params, cfg, slots=slots, binary=True, paged=True,
                      page_size=page_size, page_topn=ptn)
        _drive(eng, prompts, stagger=0)              # warm-up compile pass
        eng.reset_stats()
        gen = {}
        for p in prompts:
            gen[eng.submit(p, max_new_tokens=GEN)] = None
        while eng.queue or any(s.request is not None for s in eng.slots):
            for fr in eng.step():
                gen[fr.request_id] = list(fr.tokens)
        eng.check()
        st = eng.stats
        toks[tag] = gen
        traffic[tag] = (st["decode_pages_touched"], st["decode_hbm_bytes"])
        name = f"serve_pagesparse_{tag}_s{slots}"
        csv.append(f"{name}_pages,{st['decode_pages_touched']},"
                   f"{st['decode_hbm_bytes']}")
        csv.append(_kvpool_row(name, eng))
    dense, sparse = toks["dense"], toks[f"topn{page_topn}"]
    total = sum(len(v) for v in dense.values())
    match = sum(a == b for rid in dense
                for a, b in zip(dense[rid], sparse[rid]))
    quality = match / max(total, 1)
    dp, db = traffic["dense"]
    sp, sb = traffic[f"topn{page_topn}"]
    csv.append(f"serve_pagesparse_topn{page_topn}_quality,{quality:.3f},frac")
    print_fn(f"  page-sparse slots={slots} topn={page_topn}: decode pages "
             f"{sp} vs {dp} dense ({100 * sp / max(dp, 1):.0f}%), est HBM "
             f"{sb} vs {db} B, token match {100 * quality:.1f}%")
    assert sp < dp, (
        "page-sparse decode failed to touch fewer pages", traffic)
    assert sb < db, (
        "page-sparse decode failed to cut estimated HBM bytes", traffic)
    return csv


def _swap_case(print_fn, params, cfg, *, slots: int, n_req: int,
               stagger: int, page_size: int, swap_pages: int) -> list[str]:
    """Preemption-mechanism comparison under an overcommitted pool: the
    same staggered mixed-length workload runs with recompute preemption
    (swap off) and with page-aligned swap-out to a host pool. Recompute
    throws away every computed token of a victim and replays it; swap-out
    moves the victim's pages to host RAM and restores them verbatim, so
    its re-prefilled token count drops (to zero when every eviction
    swaps) — bit-identical outputs are pinned in tests/test_serve_ragged;
    the harness asserts the prefill-work reduction and reports the
    host-transfer byte cost that buys it."""
    from repro.serve import pages_needed
    dense_pages = slots * pages_needed(MAX_LEN, page_size)
    n_pages = max(pages_needed(MAX_LEN, page_size), int(dense_pages * 0.4))
    rng = np.random.default_rng(13)
    prompts = _prompts(max(n_req, slots + 2), "mixed", rng)
    csv, replayed = [], {}
    for swap in (0, swap_pages):
        tag = "on" if swap else "off"
        eng = _engine(params, cfg, slots=slots, binary=True, paged=True,
                      page_size=page_size, n_pages=n_pages, swap_pages=swap)
        _drive(eng, prompts, stagger=stagger)        # warm-up compile pass
        eng.reset_stats()
        r = _drive(eng, prompts, stagger=stagger)
        st = eng.stats
        name = f"serve_swapout_{tag}_s{slots}"
        t50, t95, t99 = percentiles_ms(r["ttft"])
        i50, i95, i99 = percentiles_ms(r["itl"])
        for metric, (p50, p95, p99) in (("ttft", (t50, t95, t99)),
                                        ("itl", (i50, i95, i99))):
            csv.append(f"{name}_{metric}_p50,{p50:.2f},ms")
            csv.append(f"{name}_{metric}_p95,{p95:.2f},ms")
            csv.append(f"{name}_{metric}_p99,{p99:.2f},ms")
        csv.append(f"{name}_tokens,{st['swapped_tokens']},"
                   f"{st['replayed_tokens']}")
        csv.append(_kvpool_row(name, eng))
        # per-request attribution (RequestMetrics) must re-derive the
        # scheduler's aggregate preemption counter exactly
        pa = preemption_attribution(r["metrics"])
        evictions = (pa["by_kind"].get("swap-out", 0)
                     + pa["by_kind"].get("recompute-preempt", 0))
        assert evictions == st["preemptions"], (pa, dict(st))
        csv.append(f"{name}_preempt,{pa['victims']},"
                   f"{pa['by_kind'].get('swap-out', 0)},"
                   f"{pa['by_kind'].get('recompute-preempt', 0)}")
        replayed[tag] = st["replayed_tokens"]
        if swap:
            assert st["swap_outs"] > 0, (
                "overcommit never forced a swap-out", dict(st))
            assert eng.swap.in_use == 0, "swap pool leaked reservations"
            csv.append(f"{name}_bytes,{st['swap_out_bytes']},"
                       f"{st['swap_in_bytes']}")
            print_fn(f"  swap-out  slots={slots}: {st['preemptions']} "
                     f"preemptions ({st['swap_outs']} swapped), "
                     f"{st['swapped_tokens']} tok swapped back vs "
                     f"{st['replayed_tokens']} re-prefilled | TTFT p50 "
                     f"{t50:.1f} ms | {st['swap_out_bytes']} B out / "
                     f"{st['swap_in_bytes']} B in")
        else:
            assert st["preemptions"] > 0, (
                "overcommit never preempted: case is void", dict(st))
            print_fn(f"  recompute slots={slots}: {st['preemptions']} "
                     f"preemptions, {st['replayed_tokens']} tok "
                     f"re-prefilled | TTFT p50 {t50:.1f} ms")
    assert replayed["on"] < replayed["off"], (
        "swap-out failed to reduce re-prefilled tokens", replayed)
    return csv


def _prefix_case(print_fn, params, cfg, *, slots: int, n_req: int,
                 stagger: int, page_size: int) -> list[str]:
    """Shared-system-prompt arrivals: every request is one long common
    prefix plus a short unique suffix — the repeated-long-context regime
    prefix caching exists for. The same staggered workload runs cold
    (plain paged) and with the prefix cache; the cached pass's admissions
    skip the matched prefix's prefill chunks entirely, so TTFT and
    prefill_tokens drop together (bit-identical outputs are pinned in
    tests/test_prefix_cache.py; the harness asserts the prefill-work
    reduction)."""
    rng = np.random.default_rng(11)
    sys_prompt = rng.integers(0, 512, size=2 * PROMPT_MEAN)
    suffix = min(page_size, MAX_LEN - 2 * PROMPT_MEAN - GEN)
    assert suffix >= 1, "shared prompt leaves no room for a unique suffix"
    n_lat = max(n_req, slots + 2)
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(0, 512, size=suffix)])
               for _ in range(n_lat)]
    csv, ptoks = [], {}
    for cached in (False, True):
        tag = "on" if cached else "off"
        eng = _engine(params, cfg, slots=slots, binary=True, paged=True,
                      page_size=page_size, prefix_cache=cached)
        # warm-up compiles AND (cached pass) populates the index, so the
        # timed pass measures the steady-state hit regime
        _drive(eng, prompts, stagger=stagger)
        eng.reset_stats()
        r = _drive(eng, prompts, stagger=stagger)
        st = eng.stats
        t50, t95, t99 = percentiles_ms(r["ttft"])
        name = f"serve_prefix_{tag}_s{slots}"
        csv.append(f"{name}_ttft_p50,{t50:.2f},ms")
        csv.append(f"{name}_ttft_p95,{t95:.2f},ms")
        csv.append(f"{name}_ttft_p99,{t99:.2f},ms")
        csv.append(f"{name}_prefill_tokens,{st['prefill_tokens']},tok")
        csv.append(_kvpool_row(name, eng))
        ptoks[tag] = st["prefill_tokens"]
        if cached:
            seen = st["cached_tokens"] + st["prefill_tokens"]
            rate = st["cached_tokens"] / max(seen, 1)
            pc = eng.prefix
            csv.append(f"serve_prefix_on_cached,{st['cached_tokens']},"
                       f"{rate:.3f}")
            csv.append(f"serve_prefix_on_pages,{pc.hits},{pc.registered},"
                       f"{pc.evictions}")
            print_fn(f"  prefix   slots={slots} shared-prompt: TTFT p50 "
                     f"{t50:.1f} ms, prefill {st['prefill_tokens']} tok, "
                     f"{st['cached_tokens']} cached "
                     f"({100 * rate:.0f}% hit rate, {pc.hits} page hits, "
                     f"{pc.evictions} evictions)")
        else:
            print_fn(f"  no-cache slots={slots} shared-prompt: TTFT p50 "
                     f"{t50:.1f} ms, prefill {st['prefill_tokens']} tok")
    assert ptoks["on"] < ptoks["off"], (
        "prefix cache failed to reduce prefill work", ptoks)
    return csv


def _overcommit_case(print_fn, params, cfg, *, slots: int, n_req: int,
                     page_size: int) -> list[str]:
    """Pool smaller than the dense layout's batch_slots x max_len
    reservation: the dense cache could hold only pool_tokens // max_len
    full-length residents, paging holds `slots` actual-length ones (and
    preempts/re-queues on exhaustion instead of deadlocking)."""
    from repro.serve import pages_needed
    dense_pages = slots * pages_needed(MAX_LEN, page_size)
    # large enough for any single request (submit guard), well below the
    # dense-equivalent reservation
    n_pages = max(pages_needed(MAX_LEN, page_size),
                  int(dense_pages * 0.4))
    r = _serve_case(params, cfg, slots=slots, skew="mixed", binary=True,
                    n_req=max(n_req, slots), paged=True,
                    page_size=page_size, n_pages=n_pages)
    eng = r["engine"]
    pool_tokens = n_pages * page_size
    dense_residents = pool_tokens // MAX_LEN
    st = r["stats"]
    tps = r["gen"] / r["wall"]
    print_fn(f"  overcommit slots={slots}: pool {n_pages} pages "
             f"({pool_tokens} tok) vs dense reservation "
             f"{slots * MAX_LEN} tok -> dense layout fits "
             f"{dense_residents} resident(s), paged served "
             f"{st['max_residents']} concurrently "
             f"({st['preemptions']} preemptions, {tps:.1f} tok/s)")
    assert st["max_residents"] > dense_residents, (
        "overcommit case failed to exceed dense-layout capacity")
    pa = preemption_attribution(r["metrics"])
    assert (pa["by_kind"].get("swap-out", 0)
            + pa["by_kind"].get("recompute-preempt", 0)
            == st["preemptions"]), (pa, dict(st))
    name = f"serve_paged_overcommit_s{slots}"
    return [f"{name},{r['wall'] / r['gen'] * 1e6:.1f},{tps:.2f}",
            _kvpool_row(name, eng),
            f"{name}_preempt,{pa['victims']},"
            f"{pa['by_kind'].get('recompute-preempt', 0)}"]


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload (CI): 1 slot count, 2 requests")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV cache (block tables; "
                         "adds KV-pool CSV columns + an overcommit case)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV-cache page (with --paged)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="run the shared-system-prompt case cold vs with "
                         "automatic prefix caching (implies --paged; adds "
                         "TTFT/prefill/hit-rate CSV columns)")
    ap.add_argument("--swap-pages", type=int, default=0,
                    help="run the overcommit case with recompute vs page-"
                         "aligned swap-out preemption to a host pool of "
                         "this many pages (implies --paged; adds "
                         "swapped/re-prefilled token + swap-bytes CSV "
                         "columns)")
    ap.add_argument("--page-topn", type=int, default=0,
                    help="run the two-phase page-sparse decode case: score "
                         "every resident page, attend only the top-N pages "
                         "plus the frontier (implies --paged; adds decode "
                         "pages-touched / est-HBM-bytes + quality CSV "
                         "columns)")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="run the pipelined-front-end cases: double-"
                         "buffered schedule/execute overlap vs the sync "
                         "loop (adds tok/s + overlap-fraction CSV rows) "
                         "and the open-loop Poisson goodput-under-SLO "
                         "sweep through the asyncio front end")
    ap.add_argument("--mesh-model", type=int, default=0,
                    help="run the tensor-parallel scaling sweep at mesh "
                         "model-axis sizes 1,2,..,N (implies --paged; "
                         "needs N visible devices — force host devices "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=K; asserts sharded tokens == "
                         "unsharded and a 1/N per-device pool split)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the open-loop arrival process (stamped "
                         "in the serve_openloop_meta CSV row; closed-loop "
                         "cases are unaffected)")
    ap.add_argument("--trace-file", default=None,
                    help="dump the step flight recorder + per-request "
                         "records as JSONL here after every driven "
                         "workload (schema: repro.serve.telemetry)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus-text metrics render of the "
                         "last case's registry after the run")
    ap.add_argument("--hybrid", action="store_true",
                    help="run the shared-system-prompt case on a reduced "
                         "mamba2-130m served through the pooled recurrent "
                         "state, cold vs prefix-cached (adds state-pool / "
                         "checkpoint-bytes / cached-token CSV columns; with "
                         "--swap-pages also an overcommitted state-swap "
                         "pass)")
    args = ap.parse_args()
    paged = (args.paged or args.prefix_cache or bool(args.swap_pages)
             or bool(args.page_topn) or bool(args.mesh_model))
    TELEMETRY["trace_file"] = args.trace_file
    if args.smoke:
        lines = run(slot_counts=(2,), n_req=2, paged=paged,
                    page_size=args.page_size,
                    prefix_cache=args.prefix_cache,
                    swap_pages=args.swap_pages,
                    page_topn=args.page_topn or None,
                    hybrid=args.hybrid, async_mode=args.async_mode,
                    seed=args.seed, mesh_model=args.mesh_model,
                    smoke=True)
        assert any(l.startswith("serve_env_meta,") for l in lines), lines
        assert any("_ttft_p99," in l for l in lines), lines
        assert any("_queue_p99," in l for l in lines), lines
        assert any("_stats," in l for l in lines), lines
        if paged:
            assert any("_kvpool," in l for l in lines), lines
            assert any("overcommit" in l for l in lines), lines
            assert any("_preempt," in l for l in lines), lines
        if args.prefix_cache:
            assert any("serve_prefix_on_cached," in l for l in lines), lines
            assert any(l.startswith("serve_prefix_off_") and "_ttft_p50," in l
                       for l in lines), lines
        if args.swap_pages:
            assert any(l.startswith("serve_swapout_on_") and "_tokens," in l
                       for l in lines), lines
            assert any(l.startswith("serve_swapout_on_") and "_bytes," in l
                       for l in lines), lines
            assert any(l.startswith("serve_swapout_off_") and "_ttft_p50," in l
                       for l in lines), lines
        if args.page_topn:
            assert any(l.startswith("serve_pagesparse_dense_") and "_pages,"
                       in l for l in lines), lines
            assert any(l.startswith(f"serve_pagesparse_topn{args.page_topn}_")
                       and "_pages," in l for l in lines), lines
            assert any("_quality," in l for l in lines), lines
        if args.hybrid:
            assert any(l.startswith("serve_hybrid_on_") and "_statepool,"
                       in l for l in lines), lines
            assert any(l.startswith("serve_hybrid_on_") and "_state,"
                       in l for l in lines), lines
            assert any(l.startswith("serve_hybrid_on_") and "_cached,"
                       in l for l in lines), lines
            assert any(l.startswith("serve_hybrid_off_") and
                       "_prefill_tokens," in l for l in lines), lines
            if args.swap_pages:
                assert any(l.startswith("serve_hybrid_swap_")
                           for l in lines), lines
        if args.mesh_model:
            # scaling sweep ran at every size, and the kvpool watermark
            # row at the largest size shows a NON-trivial per-device
            # split: per_device x N == total with per_device < total
            assert any(l.startswith("serve_mesh_m1,") for l in lines), lines
            assert any(l.startswith(f"serve_mesh_m{args.mesh_model},")
                       for l in lines), lines
            row = next(l for l in lines if l.startswith(
                f"serve_mesh_m{args.mesh_model}_kvpool,"))
            per_b, total_b = (int(x) for x in row.split(",")[-2:])
            assert per_b * args.mesh_model == total_b and per_b < total_b, row
            print(f"mesh smoke ok: {row}")
        if args.async_mode:
            assert any(l.startswith("serve_async_pipe_") and "_overlap,"
                       in l for l in lines), lines
            assert any(l.startswith("serve_async_sync_")
                       for l in lines), lines
            assert any(l.startswith("serve_openloop_meta,"
                                    f"{args.seed},") for l in lines), lines
            assert any(l.startswith("serve_openloop_") and "_goodput," in l
                       for l in lines), lines
        if args.trace_file:
            from repro.serve import load_trace
            events = load_trace(args.trace_file)  # validates every line
            kinds = {e["kind"] for e in events}
            assert {"meta", "step", "request", "check"} <= kinds, kinds
            steps = [e for e in events if e["kind"] == "step"]
            assert all({"schedule", "execute", "commit"}
                       <= set(e["timings"]) for e in steps), "timings missing"
            assert all(e["ok"] for e in events if e["kind"] == "check")
            print(f"trace ok: {len(events)} events")
            if args.async_mode:
                # the double-buffer's overlap must be visible in the dump
                pipe = [e for e in steps if e["timings"].get("pipelined")]
                assert pipe, "no pipelined step events in the trace"
                ratio = (sum(e["timings"]["overlap"] for e in pipe)
                         / max(sum(e["timings"]["schedule"] for e in pipe),
                               1e-9))
                assert ratio > 0, "pipelined trace records no overlap"
                print(f"async trace ok: {len(pipe)} pipelined steps, "
                      f"overlap ratio {ratio:.2f}")
        if args.metrics:
            text = TELEMETRY["last"].registry.render()
            assert "repro_serve_decode_steps" in text, text[:400]
            assert '_bucket{le="' in text, text[:400]
            print("metrics render ok")
        print("smoke ok")
    else:
        run(paged=paged, page_size=args.page_size,
            prefix_cache=args.prefix_cache, swap_pages=args.swap_pages,
            page_topn=args.page_topn or None, hybrid=args.hybrid,
            async_mode=args.async_mode, seed=args.seed,
            mesh_model=args.mesh_model)
        if args.metrics:
            print(TELEMETRY["last"].registry.render())
