#!/usr/bin/env python3
"""Smoke run of HAD serving on a TPU, through the normal serving path.

One chip (the default): smollm-135m at its published widths (30 layers,
d_model 576, 9 heads, 3 KV heads, head_dim 64, vocab 49152) with random
weights from --seed serves 8 requests (prompts of 256..1024 tokens, 32
greedy tokens each) through Engine -> Scheduler -> ModelRunner, driven by
the same loop as `python -m repro.launch.serve`. The load runs three times
on one engine design: the jnp attention path, the compiled Pallas kernels
over a dense paged walk, and the kernels with top-8 page-sparse decode.
Checks: the jnp path's first-token logits agree with the dense +-1
evaluation forward (an independent reference); the kernel path's logits
agree with the jnp path's at the first token (prefill kernel) and at every
decode step whose input tokens both paths share (paged decode kernel);
every request completes and the pools' accounting holds.

  python3 chip_smoke.py                # one chip
  python3 chip_smoke.py --four-chips   # granite-3-8b over a 1x4 mesh vs one chip

--four-chips runs only the tensor-parallel phase: granite-3-8b at its
published widths, cut to 4 layers so the single-chip reference fits, served
over a 1 data x 4 model mesh in this one process and compared with the same
engine without a mesh on the first chip, jnp and kernel paths. It prints
whether tokens and first-token logits are identical; float32 logits, first
token and shared-input decode steps, are checked against the same limit as
on one chip.

The script exits non-zero, and prints no result line, when JAX finds no TPU
or any phase fails. Its last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Times it prints are smoke timings on the host clock, not benchmarks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SLOTS, MAX_LEN, PAGE, CHUNK = 8, 2048, 16, 512
N_REQ, LEN_LO, LEN_HI, GEN = 8, 256, 1024, 32
PAGE_TOPN = 8
# float32 logits: largest |difference| allowed, as a fraction of the
# reference's largest |logit|. On a v5e, sound paths read up to 0.0251 and
# planted paged-decode faults (threshold one level high, V from the wrong
# page or kv head) 0.228 and above; the limit sits between them (PERF.md).
LOGIT_RTOL = 5e-2


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


class CompileClock:
    """Backend-compile seconds and persistent-cache hits and misses, as
    JAX's monitoring events report them (a cache hit's compile seconds
    are the time to load the executable)."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def since(self, mark: tuple) -> str:
        s, h, m = mark
        return (f"compile {self.seconds - s:.1f} s "
                f"(persistent cache: {self.hits - h} hits, "
                f"{self.misses - m} misses)")

    def mark(self) -> tuple:
        return self.seconds, self.hits, self.misses


def serve(name, cfg, params, prompts, scfg, clock):
    """Serve `prompts` on a fresh engine; returns (tokens per request,
    logits [n_req, GEN, vocab] f32 that each token was sampled from)."""
    from repro.launch.serve import drive
    from repro.serve import Engine

    mark = clock.mark()
    eng = Engine(cfg, params, scfg)
    rows: dict[int, list[np.ndarray]] = {}
    eng.runner.logits_sink = (
        lambda rid, row: rows.setdefault(rid, []).append(
            row.astype(np.float32)))
    served = drive(eng, prompts, gen=GEN)
    eng.check()
    toks = [served.tokens[r] for r in served.ids]
    check(len(toks) == len(prompts) and all(t.size == GEN for t in toks),
          f"{name}: expected {len(prompts)} x {GEN} tokens, got "
          f"{[t.size for t in toks]}")
    check(all(len(rows[r]) == GEN for r in served.ids),
          f"{name}: expected {GEN} logit rows per request, got "
          f"{[len(rows.get(r, ())) for r in served.ids]}")
    logits = np.stack([np.stack(rows[r]) for r in served.ids])
    check(bool(np.isfinite(logits).all()), f"{name}: non-finite logits")
    st = eng.stats
    print(f"[{name}] {len(toks)} requests served: "
          f"decode_steps={st['decode_steps']} "
          f"prefill_chunks={st['prefill_chunks']} "
          f"tokens_generated={st['tokens_generated']}; "
          f"{clock.since(mark)}; smoke wall {served.seconds:.1f} s "
          f"(host clock, compile included; not a benchmark)", flush=True)
    return toks, logits


def compare(name, got, want, rtol):
    """Largest |logit difference| of rows `got` [n, vocab] against `want`,
    relative to want's largest |logit|; raises above `rtol` (None: report
    only)."""
    dmax = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    top1 = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    limit = "reported, not checked" if rtol is None else f"limit {rtol:g}"
    print(f"[{name}] logits ({len(got)} rows): max |diff| {dmax:.6g} "
          f"(reference max |logit| {scale:.6g}, ratio {dmax / scale:.3g}, "
          f"{limit}); argmax agreement {top1:.3f}", flush=True)
    if rtol is not None:
        check(dmax <= rtol * scale, f"{name}: logits differ by {dmax:.6g} "
              f"> {rtol:g} x {scale:.6g}")


def shared_decode_rows(got_toks, want_toks, got, want):
    """Decode-step logit rows [n, vocab] of both paths where their inputs
    were the same. Step j of a request is fed its earlier tokens, so it is
    kept while tokens[:j] agree, up to and including the first step whose
    sampled token differs."""
    g, w = [], []
    for tg, tw, lg, lw in zip(got_toks, want_toks, got, want):
        n = min(1 + int(np.cumprod(tg == tw).sum()), GEN)
        g.append(lg[1:n])
        w.append(lw[1:n])
    return np.concatenate(g), np.concatenate(w)


def compare_paths(name, got_toks, got, want_toks, want, rtol):
    """First-token (prefill) and shared-input decode-step logits of one
    path against another, and the share of greedy tokens that match."""
    compare(f"{name}, first token", got[:, 0], want[:, 0], rtol)
    g, w = shared_decode_rows(got_toks, want_toks, got, want)
    check(len(g) > 0, f"{name}: no decode step had the same inputs")
    compare(f"{name}, decode steps with the same inputs", g, w, rtol)
    print(f"[{name}] greedy tokens matching: "
          f"{token_match(got_toks, want_toks):.4f}", flush=True)


def token_match(a, b) -> float:
    return float(np.mean([x == y for ta, tb in zip(a, b)
                          for x, y in zip(ta.tolist(), tb.tolist())]))


def reference_logits(cfg, params, prompts, n):
    """First-token logits of the dense +-1 evaluation forward (no caches,
    no kernels). Prompts are right-padded to one length: attention is
    causal, so padding after a prompt cannot change its last position."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    @jax.jit
    def last_logits(params, tokens, last):
        out = M.forward(params, {"tokens": tokens}, cfg=cfg, mode="had_eval",
                        att={"n": n})
        return out.logits[0, last, :cfg.vocab_size].astype(jnp.float32)

    pad = max(p.size for p in prompts)
    rows = []
    for p in prompts:
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, :p.size] = p
        rows.append(np.asarray(last_logits(params, jnp.asarray(tokens),
                                           jnp.int32(p.size - 1))))
    return np.stack(rows)


def one_chip(seed, clock):
    import jax
    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.launch.serve import make_prompts
    from repro.models import model as M
    from repro.models.config import HADConfig
    from repro.serve import ServeConfig

    def configs(dtype):
        return (get_config("smollm-135m", param_dtype=dtype),
                get_config("smollm-135m", param_dtype=dtype,
                           had=HADConfig(use_kernels=True)))

    cfg, _ = configs("float32")
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, {cfg.n_kv_heads} kv heads, head_dim "
          f"{cfg.dh}, vocab {cfg.vocab_size}; random weights, seed {seed}")
    print(f"pallas kernels: interpret={kops.resolve_interpret(None)}",
          flush=True)
    prompts = make_prompts(seed, N_REQ, LEN_LO, LEN_HI + 1, cfg.vocab_size)
    print(f"load: {N_REQ} requests, prompt lengths "
          f"{[int(p.size) for p in prompts]}, {GEN} greedy tokens each; "
          f"{SLOTS} slots, max_len {MAX_LEN}, page {PAGE}, "
          f"prefill_chunk {CHUNK}", flush=True)

    def scfg(**kw):
        return ServeConfig(max_len=MAX_LEN, batch_slots=SLOTS, binary=True,
                           paged=True, page_size=PAGE, prefill_chunk=CHUNK,
                           **kw)

    # float32 with full-precision matmuls: logits are compared here. In
    # bfloat16 one rounding flip moves a key across the top-N cut and 30
    # random layers amplify it, so bf16 logits of two correct paths differ
    # by a tenth of their scale (CHANGES.md).
    cfg, cfg_k = configs("float32")
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    with jax.default_matmul_precision("highest"):
        jnp_toks, jnp_logits = serve("f32 binary jnp, dense paged", cfg,
                                     params, prompts, scfg(), clock)
        mark = clock.mark()
        n = cfg.had.topn(MAX_LEN)
        ref = reference_logits(cfg, params, prompts, n)
        print(f"[f32 dense +-1 evaluation forward, N={n}] "
              f"{clock.since(mark)}", flush=True)
        compare("f32 jnp path vs evaluation forward, first token",
                jnp_logits[:, 0], ref, LOGIT_RTOL)
        k_toks, k_logits = serve("f32 binary kernels, dense paged", cfg_k,
                                 params, prompts, scfg(), clock)
    compare_paths("f32 kernel path vs jnp path", k_toks, k_logits, jnp_toks,
                  jnp_logits, LOGIT_RTOL)
    del params

    # the published dtype: served and checked for completion, finite
    # logits and pool accounting; agreement is printed, not checked
    cfg, cfg_k = configs("bfloat16")
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    jnp_toks, jnp_logits = serve("bf16 binary jnp, dense paged", cfg, params,
                                 prompts, scfg(), clock)
    k_toks, k_logits = serve("bf16 binary kernels, dense paged", cfg_k,
                             params, prompts, scfg(), clock)
    compare_paths("bf16 kernel path vs jnp path", k_toks, k_logits, jnp_toks,
                  jnp_logits, None)
    s_toks, _ = serve(f"bf16 binary kernels, top-{PAGE_TOPN} page-sparse",
                      cfg_k, params, prompts, scfg(page_topn=PAGE_TOPN),
                      clock)
    print(f"[bf16 top-{PAGE_TOPN} vs dense kernel path] greedy tokens "
          f"matching: {token_match(s_toks, k_toks):.4f} (lossy by design; "
          f"checked for completion and pool accounting only)", flush=True)


def four_chips(seed, clock):
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import make_prompts
    from repro.models import model as M
    from repro.models.config import HADConfig
    from repro.serve import ServeConfig

    n_dev = len(jax.devices())
    if n_dev < 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {n_dev}")
    cfg = get_config("granite-3-8b", n_layers=4)
    print(f"{cfg.name}: published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, {cfg.n_kv_heads} kv heads, head_dim "
          f"{cfg.dh}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}), cut to "
          f"{cfg.n_layers} of 40 layers so the single-chip reference fits; "
          f"random weights, seed {seed}", flush=True)
    prompts = make_prompts(seed, N_REQ, LEN_LO, LEN_HI + 1, cfg.vocab_size)
    print(f"load: {N_REQ} requests, prompt lengths "
          f"{[int(p.size) for p in prompts]}, {GEN} greedy tokens each",
          flush=True)
    mesh = make_host_mesh(data=1, model=4)
    print(f"mesh: 1 data x 4 model over {n_dev} {jax.devices()[0].platform} "
          f"devices; reference: no mesh, on {jax.devices()[0]}", flush=True)

    def scfg(m):
        return ServeConfig(max_len=MAX_LEN, batch_slots=SLOTS, binary=True,
                           paged=True, page_size=PAGE, prefill_chunk=CHUNK,
                           mesh=m)

    # float32 with full-precision matmuls is checked against LOGIT_RTOL,
    # as on one chip; bfloat16, the served dtype, is reported. On the CPU
    # the sharded step is bit-identical to one device (tests); on v5e
    # chips it is not (CHANGES.md).
    for dtype, short, rtol in (("float32", "f32", LOGIT_RTOL),
                               ("bfloat16", "bf16", None)):
        cfgs = [get_config("granite-3-8b", param_dtype=dtype, n_layers=4,
                           had=HADConfig(use_kernels=kernels))
                for kernels in (False, True)]
        params = M.init_params(jax.random.PRNGKey(seed), cfgs[0])
        with jax.default_matmul_precision(
                "highest" if dtype == "float32" else None):
            for label, c in zip((f"{short} binary jnp",
                                 f"{short} binary kernels"), cfgs):
                ref_toks, ref_logits = serve(f"{label}, one chip", c, params,
                                             prompts, scfg(None), clock)
                tp_toks, tp_logits = serve(f"{label}, 1x4 mesh", c, params,
                                           prompts, scfg(mesh), clock)
                same = all(np.array_equal(a, b)
                           for a, b in zip(tp_toks, ref_toks))
                first = np.array_equal(tp_logits[:, 0], ref_logits[:, 0])
                print(f"[{label}: 1x4 mesh vs one chip] greedy tokens "
                      f"identical: {same}; first-token logits identical: "
                      f"{bool(first)}", flush=True)
                compare_paths(f"{label}: 1x4 mesh vs one chip", tp_toks,
                              tp_logits, ref_toks, ref_logits, rtol)
        del params


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel phase on 4 chips")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices[0] is {dev}); this "
              f"smoke run needs the chip", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    clock = CompileClock()
    print(f"device_kind={dev.device_kind!r} device_count="
          f"{len(jax.devices())} jax={jax.__version__} compile_cache={cache}",
          flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed, clock)
    else:
        one_chip(args.seed, clock)
    print(f"total: {clock.since((0.0, 0, 0))}; smoke wall "
          f"{time.perf_counter() - t0:.1f} s (host clock)", flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
