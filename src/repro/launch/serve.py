"""Serving launcher: continuous-batching HAD inference with the packed-bit
K cache. Drives the scheduler with staggered, mixed-length requests,
streaming each request's tokens the step they commit (the scheduler's
`token_sink` hook — the same path the asyncio front end consumes).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --prompt-len 64 --gen 16 --slots 4 --requests 8 --len-spread 0.5 \
      --stagger 2

With ``--async`` the drive loop is the double-buffered
`Engine.step_pipelined()` — plan N+1 is built while step N runs on the
device — and the overlap summary is printed at exit. With
``--slo-ttft-ms`` / ``--slo-itl-ms`` the exit summary adds goodput under
SLO: the fraction of requests whose TTFT and every inter-token gap met
the deadlines (from the engine's RequestMetrics; auto-enables
telemetry), and the SLO-attaining request rate vs the raw rate.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Callable

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve import (Engine, SamplingParams, ServeConfig, Telemetry,
                         slo_attainment)


def make_prompts(seed: int, n: int, lo: int, hi: int,
                 vocab: int) -> list[np.ndarray]:
    """`n` prompts from one seed: lengths uniform in [lo, hi), token ids
    uniform in [0, vocab)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi, size=n)
    return [rng.integers(0, vocab, size=int(s)) for s in lens]


@dataclasses.dataclass
class Served:
    """What one `drive` returns: request ids in submission order, each
    request's generated tokens, the lifecycle records the engine emitted,
    and the loop's host wall time."""
    ids: list[int]
    tokens: dict[int, np.ndarray]
    metrics: list
    seconds: float


def drive(eng: Engine, prompts: list[np.ndarray], *, gen: int,
          sampling: SamplingParams | None = None, stagger: int = 0,
          warm: int = 0, pipelined: bool = False,
          on_token: Callable[[int, int, int], None] | None = None) -> Served:
    """Serve `prompts` through `eng` until every request has finished.

    With `stagger` > 0, `warm` requests are submitted up front and one
    more every `stagger` steps while residents decode; otherwise all are
    submitted at once. `pipelined` drives the double-buffered
    `step_pipelined()` loop. Every token reaches `on_token(request_id,
    index, token)` the step it commits (the scheduler's token sink), and
    the streamed sequences must equal the finished arrays.
    """
    streamed: dict[int, list[int]] = {}

    def sink(rid: int, tok: int) -> None:
        toks = streamed.setdefault(rid, [])
        toks.append(int(tok))
        if on_token is not None:
            on_token(rid, len(toks) - 1, int(tok))

    eng.scheduler.token_sink = sink
    step = eng.step_pipelined if pipelined else eng.step
    n_req = len(prompts)
    warm = min(warm, n_req) if stagger else n_req

    def submit(i: int) -> None:
        ids.append(eng.submit(prompts[i], max_new_tokens=gen,
                              sampling=sampling))

    t0 = time.perf_counter()
    ids: list[int] = []
    results: dict[int, np.ndarray] = {}
    metrics: list = []
    for i in range(warm):
        submit(i)
    next_req = warm
    steps = 0
    while eng.queue or any(s.request is not None for s in eng.slots) \
            or next_req < n_req \
            or (pipelined and eng._inflight is not None):
        for fr in step():
            results[fr.request_id] = fr.tokens
        metrics += eng.pop_finished_metrics()
        steps += 1
        if stagger and next_req < n_req and steps % stagger == 0:
            submit(next_req)
            next_req += 1
    dt = time.perf_counter() - t0
    metrics += eng.pop_finished_metrics()
    for rid in ids:
        assert streamed.get(rid, []) == results[rid].tolist(), (
            f"req {rid}: streamed tokens diverge from the finished array")
    return Served(ids, results, metrics, dt)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="mean prompt length")
    ap.add_argument("--len-spread", type=float, default=0.5,
                    help="prompt lengths drawn from mean*(1±spread)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests (default: 2x slots)")
    ap.add_argument("--stagger", type=int, default=2,
                    help="submit a new request every K decode steps "
                         "(0: all up front)")
    ap.add_argument("--prefill-chunk", type=int, default=512,
                    help="per-step prefill token budget (smaller bounds "
                         "resident ITL during admissions and lets partial "
                         "admissions carry swappable content)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--baseline", action="store_true",
                    help="full-precision attention instead of HAD")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (block tables + shared page pool)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page pool size (0: dense-equivalent capacity; "
                         "smaller overcommits and preempts on exhaustion)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="automatic prefix caching over the paged pool "
                         "(implies --paged): requests sharing a page-"
                         "aligned prompt prefix reuse its KV pages and "
                         "skip that prefill work")
    ap.add_argument("--policy", choices=("fcfs", "shortest-prompt"),
                    default="fcfs", help="admission order for the queue")
    ap.add_argument("--swap-pages", type=int, default=0,
                    help="page-aligned swap-out preemption (implies "
                         "--paged): evicted residents' KV pages move to a "
                         "host pool of this many pages and are restored "
                         "verbatim on re-admission — no re-prefill")
    ap.add_argument("--page-topn", type=int, default=0,
                    help="two-phase page-sparse decode (implies --paged): "
                         "score every resident page from its packed k_bits, "
                         "attend only the top-N pages plus the frontier. "
                         "N >= resident pages is bit-identical to dense; "
                         "small N trades accuracy for O(N*page) decode "
                         "HBM traffic")
    ap.add_argument("--victim-policy", choices=("youngest", "longest-idle"),
                    default="youngest",
                    help="which resident pays for pool pressure: the "
                         "youngest (FCFS progress) or the slot idle the "
                         "longest since its last emitted token (fairness)")
    ap.add_argument("--trace-file", default=None,
                    help="dump the step flight recorder + per-request "
                         "lifecycle records as JSONL here at exit "
                         "(schema: repro.serve.telemetry)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus-text metrics render and the "
                         "queue/TTFT/ITL percentile summary at exit")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="drive the double-buffered pipelined loop: the "
                         "scheduler builds plan N+1 while step N runs on "
                         "the device (bit-identical outputs; prints the "
                         "overlap summary at exit)")
    ap.add_argument("--stream", action="store_true",
                    help="print every token the step it commits (one "
                         "line per token) in addition to the per-request "
                         "sequences at exit")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="TTFT deadline for the goodput summary: a "
                         "request attains its SLO only if its first "
                         "token arrived within this bound (0: no TTFT "
                         "leg; enables telemetry)")
    ap.add_argument("--slo-itl-ms", type=float, default=0.0,
                    help="inter-token deadline for the goodput summary: "
                         "every gap between consecutive tokens must stay "
                         "within this bound (0: no ITL leg; enables "
                         "telemetry)")
    ap.add_argument("--fence", action="store_true",
                    help="block on the cache pools between execute and "
                         "commit so per-step execute timings measure "
                         "device time, not dispatch time (with telemetry)")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="tensor-parallel serving: shard the runner's step "
                         "over a 1 x N device mesh's model axis (params "
                         "head-sharded, KV pools sharded over kv heads, "
                         "outputs bit-identical to N=1). N must divide "
                         "n_kv_heads and fit the visible devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=K forces K host devices)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only — no decode loop")
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    n_req = args.requests or 2 * args.slots
    lo = max(1, int(args.prompt_len * (1 - args.len_spread)))
    hi = max(lo + 1, int(args.prompt_len * (1 + args.len_spread)) + 1)
    prompts = make_prompts(args.seed, n_req, lo, hi, cfg.vocab_size)
    lens = [int(p.size) for p in prompts]
    max_len = max(lens) + args.gen
    binary = not args.baseline and cfg.had.enabled and cfg.has_attention
    paged = (args.paged or args.prefix_cache or bool(args.swap_pages)
             or bool(args.page_topn))
    slo = bool(args.slo_ttft_ms or args.slo_itl_ms)
    telemetry = (Telemetry(trace_file=args.trace_file, fence=args.fence)
                 if (args.trace_file or args.metrics or args.fence or slo)
                 else None)
    mesh = None
    if args.mesh_model > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=1, model=args.mesh_model)
        print(f"mesh: 1 data x {args.mesh_model} model over "
              f"{len(jax.devices())} {jax.default_backend()} device(s)")
    eng = Engine(cfg, params, ServeConfig(max_len=max_len,
                                          batch_slots=args.slots,
                                          prefill_chunk=args.prefill_chunk,
                                          binary=binary, paged=paged,
                                          page_size=args.page_size,
                                          n_pages=args.n_pages or None,
                                          policy=args.policy,
                                          prefix_cache=args.prefix_cache,
                                          swap_pages=args.swap_pages,
                                          victim_policy=args.victim_policy,
                                          page_topn=args.page_topn or None,
                                          mesh=mesh),
                 telemetry=telemetry)
    if mesh is not None:
        total_b, per_b = eng.runner.cache_device_bytes()
        print(f"  kv pools: {total_b} bytes total, {per_b} per device")
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, seed=args.seed)

    def on_token(rid: int, i: int, tok: int) -> None:
        print(f"  + req {rid}[{i}] = {tok}", flush=True)

    served = drive(eng, prompts, gen=args.gen, sampling=sampling,
                   stagger=args.stagger, warm=args.slots,
                   pipelined=args.async_mode,
                   on_token=on_token if args.stream else None)
    ids, results, dt = served.ids, served.tokens, served.seconds
    req_metrics = served.metrics

    gen_tok = eng.stats["tokens_generated"]
    print(f"arch={cfg.name} binary={binary} N={eng.n} slots={args.slots} "
          f"requests={n_req} prompt_lens={lens} gen={args.gen}")
    for rid in ids:
        print(f"  req {rid}: {results[rid].tolist()}")
    print(f"wall {dt:.2f}s  decode_steps={eng.stats['decode_steps']} "
          f"prefill_chunks={eng.stats['prefill_chunks']} "
          f"({gen_tok / dt:.1f} generated tok/s)")
    if args.async_mode:
        ov = eng.overlap_stats()
        print(f"pipeline: {ov['pipelined_steps']} double-buffered steps, "
              f"{100 * ov['overlap_frac']:.0f}% of scheduling overlapped "
              f"with device execution "
              f"({ov['overlap_s'] * 1e3:.1f}/{ov['schedule_s'] * 1e3:.1f} "
              f"ms)")
    if paged:
        a = eng.allocator
        print(f"kv pool: peak {a.peak_in_use}/{a.n_pages} pages "
              f"x {a.page_size} tok, {eng.stats['preemptions']} preemptions, "
              f"max {eng.stats['max_residents']} concurrent residents")
        mode = (f"top-{args.page_topn} page-sparse" if args.page_topn
                else "dense")
        print(f"decode traffic ({mode}): "
              f"{eng.stats['decode_pages_touched']} pages attended, "
              f"~{eng.stats['decode_hbm_bytes']} B KV read")
    if args.prefix_cache:
        pc = eng.prefix
        print(f"prefix cache: {eng.stats['cached_tokens']} prompt tok "
              f"served from cached pages ({pc.hits} page hits, "
              f"{pc.registered} registered, {pc.evictions} evicted, "
              f"{len(pc)} resident entries)")
    if args.swap_pages:
        sw = eng.swap
        print(f"swap pool: {eng.stats['swap_outs']} swap-outs / "
              f"{eng.stats['swap_ins']} swap-ins (peak {sw.peak_in_use}/"
              f"{sw.capacity} pages), {eng.stats['swapped_tokens']} tok "
              f"restored without re-prefill vs "
              f"{eng.stats['replayed_tokens']} recomputed, "
              f"{eng.stats['swap_out_bytes']} B out / "
              f"{eng.stats['swap_in_bytes']} B in")

    if telemetry is not None:
        def pcts(xs):
            if not xs:
                return "n/a"
            ms = np.asarray(xs, np.float64) * 1e3
            p = [float(np.percentile(ms, q)) for q in (50, 95, 99)]
            return f"{p[0]:.1f}/{p[1]:.1f}/{p[2]:.1f} ms"

        by_id = sorted(req_metrics, key=lambda m: m.request_id)
        ttft = [m.ttft for m in by_id if m.ttft is not None]
        queue = [m.queue_time for m in by_id if m.queue_time is not None]
        itl = [s for m in by_id for s in m.itl]
        print(f"latency (p50/p95/p99): queue {pcts(queue)} | "
              f"TTFT {pcts(ttft)} | ITL {pcts(itl)}")
        if slo:
            att = slo_attainment(
                req_metrics,
                ttft_s=args.slo_ttft_ms / 1e3 if args.slo_ttft_ms else None,
                itl_s=args.slo_itl_ms / 1e3 if args.slo_itl_ms else None)
            legs = []
            if args.slo_ttft_ms:
                legs.append(f"TTFT<={args.slo_ttft_ms:g}ms")
            if args.slo_itl_ms:
                legs.append(f"ITL<={args.slo_itl_ms:g}ms")
            print(f"SLO ({', '.join(legs)}): {att['attained']}/"
                  f"{att['total']} requests attained "
                  f"({100 * att['attainment']:.0f}%) | goodput "
                  f"{att['attained'] / dt:.2f} req/s of "
                  f"{att['total'] / dt:.2f} req/s served")
        victims = [m for m in by_id
                   if any(n for k, n in m.preemptions.items()
                          if k != "lru-evict")]
        if victims:
            print(f"preempted requests ({len(victims)}):")
            for m in victims:
                kinds = ", ".join(f"{k} x{n}"
                                  for k, n in sorted(m.preemptions.items())
                                  if n)
                print(f"  req {m.request_id}: {kinds}, "
                      f"{m.swapped_tokens} tok swapped back, "
                      f"{m.replayed_tokens} replayed, "
                      f"{m.swap_out_bytes} B out")
        if args.metrics:
            print(telemetry.registry.render())
        if args.trace_file:
            n = eng.dump_trace(requests=req_metrics)
            print(f"wrote {n} trace events -> {args.trace_file}")
        else:
            eng.check()


if __name__ == "__main__":
    main()
