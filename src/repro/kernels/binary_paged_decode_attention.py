"""Pallas TPU kernel: fused HAD decode attention over a PAGED KV cache.

Same two-pass exact-top-N structure as binary_decode_attention (score
histogram -> threshold -> masked exp accumulation), but K/V live in shared
page pools with no batch axis:

  k_pool: [n_pages, Hk, W, page]  uint32 bit-planes
  v_pool: [n_pages, Hk, page, Dv]

and each (batch, kv-head) row walks its OWN row of a block table instead
of a contiguous cache. The block table is a *scalar-prefetch* operand
(PrefetchScalarGridSpec) that the K/V BlockSpec index maps read to pick
the physical pages DMA'd for each grid step.

Grid: (B*Hk, 2, n_blocks) — sequential on TPU; VMEM scratch carries the
histogram/threshold/accumulators across the two passes of each (batch,
kv-head) row, as in the contiguous kernel.

Blocked walk. A grid step covers a block of P = ``pages_per_block(page)``
listed pages (32 at page 16: 512 tokens), not one page: the kernel takes
P K inputs and P V inputs, the p-th of each holding the block's p-th
page, and concatenates them into one [W, P*page] K tile and one
[P*page, Dv] V tile, so the fixed cost of a grid step is paid once per
block and the arithmetic is the contiguous kernel's at block_t = P*page.
(The pages cannot be DMA'd one by one from a pool left in HBM: Mosaic
lays a 16-lane page dim out on a 128-lane tile and refuses the slice, so
they come through BlockSpecs.)

Residency bound. A scalar-prefetched live count per table row — one past
its last listed page with a nonzero count — ends the walk: blocks at or
past it compute nothing, and their index maps repeat the page each input
last held, so the pipeline issues no copy. The threshold and the output
still fire once per row, on its last live block (block 0 for a row with
nothing resident, whose output stays zero).

Each page is fetched once. Pass 0 reads K and keeps each block's masked
scores in VMEM ([n_blocks, G, P*page] int32), so pass 1 reads no K: its K
index maps hold pass 0's last block. V moves only in pass 1, for live
blocks: in pass 0 the V index maps hold block 0, which pass 1 reads
first. (A block that ``block_skip`` proves holds no kept key is not
computed, but its V is still fetched: index maps see only the
scalar-prefetched operands, not the skip list.) Whatever a tile holds
past its page's count — a partial page, a count-0 page, a stale page an
input kept from an earlier block — is masked to exact zeros in the
scores' validity and in V itself, so not even a NaN leaks in.

A table row serves ``rep`` consecutive (batch, kv-head) grid rows: the
dense path passes one row per slot (rep = Hk), so its tables and counts,
both held in SMEM, do not grow with the kv-head count, while a
*compacted* table of selected pages (top-N page-sparse decode, phase 2)
has one row per (batch, kv-head) (rep = 1). Because compaction breaks
the ``i*page + off`` logical position arithmetic, per-token validity
comes from ``counts[r, i]`` — the number of valid tokens in table row
r's i-th listed page — instead of a per-row total length. Pages are
listed in ascending logical order and blocks start at listed page 0, so
the floating-point result is bit-identical to the contiguous kernel with
block_t == P*page whenever the listed pages cover the context, and a
compacted table whose resident pages come first walks them in the same
blocks as the dense table.
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

BLOCK_TOKENS = 512      # tokens a grid step aims to cover
MAX_BLOCK_PAGES = 32    # pages a grid step takes at most (2 inputs each)


def pages_per_block(page: int) -> int:
    """Listed pages per grid step: a function of the page size alone, so
    a dense table and a compacted one group their pages alike."""
    return max(1, min(MAX_BLOCK_PAGES, BLOCK_TOKENS // page))


def _listed_index(i, p: int, n_live, pp: int):
    """Index, in its table row, of the page the p-th K/V input holds at
    block i: block i's p-th page while that page is live, else the last
    live page this input held (an unchanged block index, so no copy),
    else (a row with fewer than p + 1 live pages) listed page p."""
    last = jnp.maximum(n_live - 1 - p, 0) // pp
    return jnp.minimum(i, last) * pp + p


def _paged_decode_kernel(bt_ref, nlive_ref, cnt_ref, nsel_ref, scale_ref,
                         q_ref, *refs,
                         d: int, page: int, pp: int, rep: int,
                         block_skip: bool):
    k_refs, v_refs = refs[:pp], refs[pp:2 * pp]
    (o_ref, hist_ref, thr_ref, num_ref, den_ref, blkmax_ref,
     thrmin_ref, s_ref) = refs[2 * pp:]
    bh = pl.program_id(0)
    ph = pl.program_id(1)
    i = pl.program_id(2)
    r = bh // rep
    n_blocks = (nlive_ref[r] + pp - 1) // pp     # live blocks of the row
    last = jnp.maximum(n_blocks, 1) - 1
    live = i < n_blocks

    q = q_ref[0]            # [G, W]

    def page_counts():
        return [cnt_ref[r, i * pp + p] for p in range(pp)]

    def scores_valid(cnts):
        k = jnp.concatenate([k_ref[0, 0] for k_ref in k_refs], axis=1)
        s = _scores(q, k, d)    # [G, P*page] int32
        # token t of page p is valid iff t < p*page + count(p)
        ends = jnp.concatenate(
            [jnp.full((1, page), p * page + c, jnp.int32)
             for p, c in enumerate(cnts)], axis=1)
        lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return s, lane < ends

    @pl.when((ph == 0) & (i == 0))
    def _init_hist():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    if block_skip:
        def _block_live():
            return blkmax_ref[i] >= thrmin_ref[0]
    else:
        def _block_live():
            return jnp.asarray(True)

    @pl.when(live & (ph == 0))
    def _accum_hist():
        s, valid = scores_valid(page_counts())
        hist_ref[...] += _at_or_above(s, valid, d)
        sm = jnp.where(valid, s, -d - 2)  # below every threshold (>= -d)
        s_ref[i] = sm
        if block_skip:
            blkmax_ref[i] = jnp.max(sm)

    @pl.when(live & (ph == 1) & _block_live())
    def _accum_softmax():
        cnts = page_counts()
        s = s_ref[i]
        keep = s >= thr_ref[...]
        e = jnp.where(
            keep, jnp.exp(scale_ref[0] * (s - d).astype(jnp.float32)),
            0.0)
        rows = jax.lax.broadcasted_iota(jnp.int32,
                                        v_refs[0].shape[2:], 0)
        held = jnp.concatenate([rows < c for c in cnts], axis=0)
        v = jnp.where(held, jnp.concatenate(
            [v_ref[0, 0] for v_ref in v_refs], axis=0), 0)  # [P*page, Dv]
        num_ref[...] += jax.lax.dot_general(
            e, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        den_ref[...] += jnp.sum(e, axis=-1, keepdims=True)

    @pl.when((ph == 0) & (i == last))
    def _finalize_threshold():
        thr = _threshold(hist_ref[...], nsel_ref[0], d)
        thr_ref[...] = thr
        if block_skip:
            thrmin_ref[0] = jnp.min(thr)
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    @pl.when((ph == 1) & (i == last))
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
      block_tables: [R, n_listed] int32 physical page ids, R dividing
        B*Hk: table row r serves grid rows r*rep .. r*rep + rep-1,
        rep = B*Hk // R (R = B: one table per slot; R = B*Hk: one per
        (batch, kv-head) row). Entries are >= 0; those with count 0 may
        alias any page — masked. Rows list their pages in ascending
        logical order; a compacted table (page-sparse phase 2) lists
        only the selected pages, resident ones first.
      d: head dimension (bits).
      nsel: [1] int32 top-N; scale: [1] float32 logit scale.
      counts: [R, n_listed] int32 valid tokens per listed page. The walk
        ends after each row's last page with a nonzero count.
      n_kv_heads: Hk (maps grid row -> kv head for the pool index).

    Tables and counts are padded here to a whole number of blocks of
    ``pages_per_block(page)`` pages, with page id 0 and count 0.

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
    pp = pages_per_block(page)
    n_blocks = pl.cdiv(nb, pp)
    pad = ((0, 0), (0, n_blocks * pp - nb))
    tables = jnp.pad(block_tables.astype(jnp.int32), pad)
    counts = jnp.pad(counts.astype(jnp.int32), pad)
    listed = jnp.arange(1, nb + 1, dtype=jnp.int32)
    n_live = jnp.max(jnp.where(counts[:, :nb] > 0, listed, 0), axis=1)

    def k_map(p):                       # pass 1 holds pass 0's last block
        def index(bh, ph, i, bt, nl):
            j = _listed_index(i + ph * (n_blocks - 1 - i), p,
                              nl[bh // rep], pp)
            return bt[bh // rep, j], bh % n_kv_heads, 0, 0
        return index

    def v_map(p):                       # pass 0 holds block 0
        def index(bh, ph, i, bt, nl):
            j = _listed_index(i * ph, p, nl[bh // rep], pp)
            return bt[bh // rep, j], bh % n_kv_heads, 0, 0
        return index

    kernel = functools.partial(_paged_decode_kernel, d=d, page=page, pp=pp,
                               rep=rep, block_skip=block_skip)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # tables and live counts
        grid=(bhk, 2, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # counts [R, nb]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # nsel [1]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scale [1]
            pl.BlockSpec((1, g, w), lambda bh, ph, i, bt, nl: (bh, 0, 0)),
        ] + [pl.BlockSpec((1, 1, w, page), k_map(p)) for p in range(pp)]
          + [pl.BlockSpec((1, 1, page, dv), v_map(p)) for p in range(pp)],
        out_specs=pl.BlockSpec((1, g, dv),
                               lambda bh, ph, i, bt, nl: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d + 1), jnp.int32),   # at-or-above counts
            pltpu.VMEM((g, 1), jnp.int32),       # threshold
            pltpu.VMEM((g, dv), jnp.float32),    # numerator
            pltpu.VMEM((g, 1), jnp.float32),     # denominator
            pltpu.SMEM((n_blocks,), jnp.int32),  # per-block max (skip list)
            pltpu.SMEM((1,), jnp.int32),         # min threshold over rows
            pltpu.VMEM((n_blocks, g, pp * page), jnp.int32),  # pass-0 scores
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhk, g, dv), jnp.float32),
        interpret=interpret,
    )(tables, n_live, counts, nsel, scale, q_bits,
      *([k_pool] * pp), *([v_pool] * pp))
