"""Kernel microbenchmarks + analytic TPU projections.

CPU wall-clock of the interpret-mode kernels is correctness-grade only
(Python-executed bodies); the value here is (a) the jnp reference path's
actual wall time vs a dense f32 attention baseline on CPU — the op-count
reduction is real on any backend — and (b) analytic v5e projections of the
fused decode kernel's bytes/time vs a bf16 dense-attention decode.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attention as A
from repro.core import hamming
from repro.kernels import ops
from repro.launch.roofline import MODELED_KIND, chip_peaks

HBM_BW = chip_peaks(MODELED_KIND).hbm_bw


def _time(f, iters=5):
    jax.block_until_ready(f())
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f())
    return (time.perf_counter() - t0) / iters * 1e6


def decode_projection(ctx: int, *, d=128, hk=8, g=8, n=None) -> dict:
    """Analytic v5e time for one decode token, one layer's attention."""
    n = n if n is not None else max(int(0.117 * ctx), 16)
    w = hamming.packed_words(d)
    dense_bytes = ctx * d * 2 * 2 * hk          # K + V bf16 reads
    had_bytes = ctx * w * 4 * hk + n * d * 2 * hk  # packed K + top-N V rows
    dense_t = dense_bytes / HBM_BW
    had_t = had_bytes / HBM_BW
    return {"ctx": ctx, "n": n, "dense_us": dense_t * 1e6,
            "had_us": had_t * 1e6, "speedup": dense_t / had_t}


def page_sparse_projection(ctx: int, *, d=128, hk=8, page=64,
                           topn_pages: int | None = None) -> dict:
    """Analytic v5e bytes for one paged decode token, one layer.

    Dense paged decode walks every resident page: packed K bit-planes +
    bf16 V for the whole context. Two-phase page-sparse decode re-reads
    the packed K twice (phase-1 scoring touches every page's k_bits,
    phase-2 re-reads the selected pages') but fetches V only for the
    top-N pages — and V dominates (d*2 bytes/token vs d/8 packed), so
    traffic drops toward O(topn_pages * page) as context grows."""
    n = max(int(0.117 * ctx), 16)
    if topn_pages is None:
        topn_pages = max(-(-n // page), 1)      # pages covering top-N tokens
    w = hamming.packed_words(d)
    dense_bytes = (ctx * w * 4 + ctx * d * 2) * hk
    sel_tok = min(topn_pages * page, ctx)
    sparse_bytes = (ctx * w * 4 + sel_tok * (w * 4 + d * 2)) * hk
    return {"ctx": ctx, "pages": topn_pages,
            "dense_us": dense_bytes / HBM_BW * 1e6,
            "sparse_us": sparse_bytes / HBM_BW * 1e6,
            "speedup": dense_bytes / sparse_bytes}


def _paged_sparse_case(print_fn) -> list[str]:
    """CPU wall-clock of ops.paged_decode_attention dense vs two-phase
    page-sparse (interpret mode: correctness-grade timing, but the same
    jitted entry points the serving engine calls)."""
    b, hk, g, d, page, nb = 1, 4, 2, 64, 16, 16
    h, w = hk * g, hamming.packed_words(d)
    ctx = nb * page
    rng = np.random.default_rng(1)
    qb = jnp.asarray(rng.integers(0, 2**32, size=(b, h, w), dtype=np.uint64)
                     .astype(np.uint32))
    n_pages = nb + 2   # leave slack ids so tables exercise the gather
    k_pool = jnp.asarray(rng.integers(0, 2**32, size=(n_pages, hk, w, page),
                                      dtype=np.uint64).astype(np.uint32))
    v_pool = jnp.asarray(rng.normal(size=(n_pages, hk, page, d))
                         .astype(np.float32))
    bt = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    lengths = jnp.asarray([ctx], jnp.int32)
    csv = []

    def _call(ptn):
        return ops.paged_decode_attention(
            qb, k_pool, v_pool, bt, d=d, nsel=32, scale=d ** -0.5,
            lengths=lengths, page_topn=ptn)

    t_dense = _time(lambda: _call(None))
    t_sparse = _time(lambda: _call(4))
    print_fn(f"paged decode kernel, ctx={ctx} ({nb} pages): dense "
             f"{t_dense:.0f}us  page-sparse(top4) {t_sparse:.0f}us "
             f"(CPU interpret; ratio {t_dense / t_sparse:.2f})")
    csv.append(f"kernel_paged_sparse,{t_sparse:.1f},dense_us={t_dense:.1f}")

    print_fn("v5e paged-decode projection (per layer, bytes-bound):")
    print_fn(f"{'ctx':>8} {'pages':>6} {'dense_us':>9} {'sparse_us':>9} "
             f"{'x':>6}")
    for ctx_p in (32_768, 131_072, 524_288):
        p = page_sparse_projection(ctx_p)
        print_fn(f"{p['ctx']:>8} {p['pages']:>6} {p['dense_us']:>9.1f} "
                 f"{p['sparse_us']:>9.1f} {p['speedup']:>6.2f}")
        csv.append(f"kernel_paged_sparse_v5e_{ctx_p},{p['sparse_us']:.1f},"
                   f"speedup={p['speedup']:.2f}")
    return csv


def run(print_fn=print) -> list[str]:
    csv = []
    # CPU wall-clock: jnp HAD inference path vs dense f32 attention
    b, h, hk, s, d, n = 1, 8, 8, 2048, 64, 240
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, hk, s, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, hk, s, d)).astype(np.float32))
    qb, kb = hamming.pack_bits(q), hamming.pack_bits(k)

    dense = jax.jit(lambda q, k, v: A.standard_attention(
        q, k, v, scale=d ** -0.5, causal=False))
    had = jax.jit(lambda qb, kb, v: A.had_infer_attention(
        qb, kb, v, d=d, n=n, scale=d ** -0.5, causal=False))
    t_dense = _time(lambda: dense(q, k, v))
    t_had = _time(lambda: had(qb, kb, v))
    print_fn(f"decode jnp path, ctx={s}: dense {t_dense:.0f}us  "
             f"had {t_had:.0f}us (CPU; ratio {t_dense / t_had:.2f})")
    csv.append(f"kernel_decode_jnp,{t_had:.1f},dense_us={t_dense:.1f}")

    # analytic v5e projections across context
    print_fn("v5e decode-attention projection (per layer, bytes-bound):")
    print_fn(f"{'ctx':>8} {'N':>6} {'dense_us':>9} {'had_us':>8} {'x':>6}")
    for ctx in (32_768, 131_072, 524_288):
        p = decode_projection(ctx)
        print_fn(f"{p['ctx']:>8} {p['n']:>6} {p['dense_us']:>9.1f} "
                 f"{p['had_us']:>8.1f} {p['speedup']:>6.2f}")
        csv.append(f"kernel_decode_v5e_{ctx},{p['had_us']:.1f},"
                   f"speedup={p['speedup']:.2f}")
    csv += _paged_sparse_case(print_fn)
    return csv


if __name__ == "__main__":
    for line in run():
        print(line)
