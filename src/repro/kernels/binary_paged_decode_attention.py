"""Pallas TPU kernel: fused HAD decode attention over a PAGED KV cache.

Same two-pass exact-top-N structure as binary_decode_attention (score
histogram -> threshold -> masked exp accumulation), but K/V live in shared
page pools with no batch axis:

  k_pool: [n_pages, Hk, W, page]  uint32 bit-planes
  v_pool: [n_pages, Hk, page, Dv]

and each (batch, kv-head) row walks its OWN row of a block table instead
of a contiguous cache. The block table is a *scalar-prefetch* operand
(PrefetchScalarGridSpec): the K/V BlockSpec index maps read
``block_tables[bh, i]`` to pick the physical page DMA'd for sequence
block i — the "block-table prefetch inner loop".

A table row serves ``rep`` consecutive (batch, kv-head) grid rows: the
dense path passes one row per slot (rep = Hk), so its tables and counts,
both held in SMEM, do not grow with the kv-head count, while a
*compacted* table of selected pages (top-N page-sparse decode, phase 2)
has one row per (batch, kv-head) (rep = 1). Because compaction breaks
the ``i*page + off`` logical position arithmetic, per-token validity
comes from ``counts[r, i]`` — the number of valid tokens in table row
r's i-th listed block — instead of a per-row total length. Blocks are listed in ascending logical order, so
the accumulation order (and thus the floating-point result) is
bit-identical to the contiguous kernel with block_t == page whenever the
listed blocks cover the context.

Grid: (B*Hk, 2, n_blocks) — sequential on TPU; VMEM scratch carries the
histogram/threshold/accumulators across passes within each (batch,
kv-head), exactly as in the contiguous kernel. Blocks with count 0
(garbage / padding entries) contribute nothing (the wrapper clamps their
page ids so the index map stays in range).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.binary_decode_attention import (_at_or_above, _scores,
                                                   _threshold)

Array = jax.Array


def _paged_decode_kernel(bt_ref, cnt_ref, nsel_ref, scale_ref,
                         q_ref, k_ref, v_ref, o_ref,
                         hist_ref, thr_ref, num_ref, den_ref, blkmax_ref,
                         thrmin_ref, *,
                         d: int, page: int, rep: int, block_skip: bool):
    bh = pl.program_id(0)
    ph = pl.program_id(1)
    i = pl.program_id(2)
    nb = pl.num_programs(2)

    q = q_ref[0]            # [G, W]

    def scores_valid():
        k = k_ref[0, 0]         # [W, page] — page picked by the index map
        s = _scores(q, k, d)    # [G, page] int32
        off = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return s, off < cnt_ref[bh // rep, i]

    @pl.when((ph == 0) & (i == 0))
    def _init_hist():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(ph == 0)
    def _accum_hist():
        s, valid = scores_valid()
        hist_ref[...] += _at_or_above(s, valid, d)
        if block_skip:
            blkmax_ref[i] = jnp.max(jnp.where(valid, s, -d - 2))

    @pl.when((ph == 0) & (i == nb - 1))
    def _finalize_threshold():
        thr = _threshold(hist_ref[...], nsel_ref[0], d)
        thr_ref[...] = thr
        if block_skip:
            thrmin_ref[0] = jnp.min(thr)
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    if block_skip:
        def _block_live():
            return blkmax_ref[i] >= thrmin_ref[0]
    else:
        def _block_live():
            return jnp.asarray(True)

    @pl.when((ph == 1) & _block_live())
    def _accum_softmax():
        s, valid = scores_valid()
        keep = jnp.logical_and(s >= thr_ref[...], valid)
        e = jnp.where(keep,
                      jnp.exp(scale_ref[0] * (s - d).astype(jnp.float32)),
                      0.0)
        num_ref[...] += jax.lax.dot_general(
            e, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        den_ref[...] += jnp.sum(e, axis=-1, keepdims=True)

    @pl.when((ph == 1) & (i == nb - 1))
    def _write_out():
        o_ref[0] = num_ref[...] / jnp.maximum(den_ref[...], 1e-30)


def paged_decode_attention(q_bits: Array, k_pool: Array, v_pool: Array,
                           block_tables: Array, *, d: int, nsel: Array,
                           scale: Array, counts: Array,
                           n_kv_heads: int, interpret: bool = True,
                           block_skip: bool = True) -> Array:
    """Fused HAD decode attention over paged K/V pools.

    Args:
      q_bits: [B*Hk, G, W] uint32 — new-token query bits per KV head.
      k_pool: [n_pages, Hk, W, page] uint32 — paged K bit-planes.
      v_pool: [n_pages, Hk, page, Dv] — paged V.
      block_tables: [R, n_blocks] int32 physical page ids, R dividing
        B*Hk: table row r serves grid rows r*rep .. r*rep + rep-1,
        rep = B*Hk // R (R = B: one table per slot; R = B*Hk: one per
        (batch, kv-head) row). Entries are >= 0; those with count 0 may
        alias any page — masked. Rows list their blocks in ascending
        logical order; a compacted table (page-sparse phase 2) lists only
        the selected pages.
      d: head dimension (bits).
      nsel: [1] int32 top-N; scale: [1] float32 logit scale.
      counts: [R, n_blocks] int32 valid tokens per listed block.
      n_kv_heads: Hk (maps grid row -> kv head for the pool index).

    Returns: [B*Hk, G, Dv] float32 attention outputs.
    """
    bhk, g, w = q_bits.shape
    n_pages_k, hk, w2, page = k_pool.shape
    n_pages_v, hk2, page2, dv = v_pool.shape
    assert w == w2 and page == page2 and hk == hk2 == n_kv_heads
    assert n_pages_k == n_pages_v
    r, nb = block_tables.shape
    assert bhk % r == 0 and counts.shape == (r, nb), \
        (block_tables.shape, counts.shape, bhk)
    rep = bhk // r
    kernel = functools.partial(_paged_decode_kernel, d=d, page=page,
                               rep=rep, block_skip=block_skip)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # block_tables feeds the index maps
        grid=(bhk, 2, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # counts [R, nb]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # nsel [1]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scale [1]
            pl.BlockSpec((1, g, w), lambda bh, ph, i, bt: (bh, 0, 0)),
            pl.BlockSpec((1, 1, w, page),
                         lambda bh, ph, i, bt: (bt[bh // rep, i],
                                                bh % n_kv_heads, 0, 0)),
            pl.BlockSpec((1, 1, page, dv),
                         lambda bh, ph, i, bt: (bt[bh // rep, i],
                                                bh % n_kv_heads, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, dv), lambda bh, ph, i, bt: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d + 1), jnp.int32),   # at-or-above counts
            pltpu.VMEM((g, 1), jnp.int32),       # threshold
            pltpu.VMEM((g, dv), jnp.float32),    # numerator
            pltpu.VMEM((g, 1), jnp.float32),     # denominator
            pltpu.SMEM((nb,), jnp.int32),        # per-block max (skip list)
            pltpu.SMEM((1,), jnp.int32),         # min threshold over rows
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhk, g, dv), jnp.float32),
        interpret=interpret,
    )(block_tables, counts, nsel, scale, q_bits, k_pool, v_pool)
