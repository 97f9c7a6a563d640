"""Pallas TPU kernel: phase-1 page scoring for top-N page-sparse decode.

Scores every resident page of a (slot, kv-head) row with an UPPER BOUND
on the Hamming attention score any valid key in that page can reach
against the row's group queries, using only the page's stored ``k_bits``
bit-planes — no fp K, no V, no extra metadata to maintain.

For a query q and key k* (both d bits), score(q, k*) = d - 2*ham(q, k*)
= 2*(#bit matches) - d. Per bit j, let cnt_j be the number of VALID keys
in the page with bit j set (a popcount over the page axis of the stored
bit-planes). Some valid key can match q at bit j iff

  q_j = 1 and cnt_j > 0,   or   q_j = 0 and cnt_j < n_valid.

Summing this "matchable" indicator over the d bits bounds #matches for
EVERY individual key in the page, so

  ub = 2 * sum_j matchable_j - d  >=  max over valid keys of score(q, k*)

The per-page score is the max of ub over the G group queries. Ranking
pages by ub and attending only the winners (plus the frontier page) can
therefore only drop pages whose best key is beatable — at
page_topn >= resident pages nothing is dropped and the result is
bit-identical to dense paged decode.

Grid: (B*Hk, n_blocks); the block table is a scalar-prefetch operand
exactly as in the phase-2 decode kernel, one row per slot, and per-block
valid counts live in SMEM. Phase 1 reads O(context * d/8) bytes of bit-planes; phase 2
then reads only the selected pages' k_bits AND v — the O(context) fp V
gather is what this pass eliminates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _page_score_kernel(bt_ref, cnt_ref, q_ref, k_ref, o_ref, *,
                       d: int, page: int, n_kv_heads: int):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    nv = cnt_ref[bh // n_kv_heads, i]       # valid tokens in this block
    k = k_ref[0, 0]                         # [W, page] uint32 bit-planes
    w = k.shape[0]
    off = jax.lax.broadcasted_iota(jnp.int32, (w, page), 1)
    kv = jnp.where(off < nv, k, jnp.uint32(0))
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 32, 1), 1)
    bits = jax.lax.shift_right_logical(kv[:, None, :], shifts) & jnp.uint32(1)
    cnt = jnp.sum(bits.astype(jnp.int32), axis=2).reshape(1, w * 32)
    q = q_ref[0]                            # [G, W] uint32
    g = q.shape[0]
    qshift = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 32), 2)
    qbit = (jax.lax.shift_right_logical(q[:, :, None], qshift)
            & jnp.uint32(1)).reshape(g, w * 32)
    match = jnp.where(qbit == jnp.uint32(1), (cnt > 0).astype(jnp.int32),
                      (cnt < nv).astype(jnp.int32))
    live = jax.lax.broadcasted_iota(jnp.int32, (1, w * 32), 1) < d
    match = jnp.where(live, match, 0)       # zero-padded tail bits: ignore
    ub = 2 * jnp.sum(match, axis=1, keepdims=True) - d
    # the row's scores stay resident across i: fill lane i by a select
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, o_ref.shape[-1]), 1)
    o_ref[0] = jnp.where(lane == i, jnp.max(ub, axis=0, keepdims=True),
                         o_ref[0])


def paged_page_scores(q_bits: Array, k_pool: Array, block_tables: Array,
                      counts: Array, *, d: int, n_kv_heads: int,
                      interpret: bool = True) -> Array:
    """Upper-bound Hamming page scores over a paged K bit-plane pool.

    Args:
      q_bits: [B*Hk, G, W] uint32 — new-token query bits per KV head.
      k_pool: [n_pages, Hk, W, page] uint32 — paged K bit-planes.
      block_tables: [B, n_blocks] int32 physical page ids per slot
        (>= 0; entries with count 0 may alias any page — their score is
        -d and the caller masks them out of selection anyway).
      counts: [B, n_blocks] int32 valid tokens per listed block.
      d: head dimension (bits). n_kv_heads: Hk.

    Returns: [B*Hk, n_blocks] int32 per-page upper-bound scores (max
    over the G group queries; lattice {-d..d}).
    """
    bhk, g, w = q_bits.shape
    n_pages, hk, w2, page = k_pool.shape
    assert w == w2 and hk == n_kv_heads
    b, nb = block_tables.shape
    assert b * hk == bhk and counts.shape == (b, nb)
    kernel = functools.partial(_page_score_kernel, d=d, page=page,
                               n_kv_heads=hk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bhk, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # counts [B, nb]
            pl.BlockSpec((1, g, w), lambda bh, i, bt: (bh, 0, 0)),
            pl.BlockSpec((1, 1, w, page),
                         lambda bh, i, bt: (bt[bh // n_kv_heads, i],
                                            bh % n_kv_heads, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nb), lambda bh, i, bt: (bh, 0, 0)),
        scratch_shapes=[],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhk, 1, nb), jnp.int32),
        interpret=interpret,
    )(block_tables, counts, q_bits, k_pool)
    return out.reshape(bhk, nb)


def page_score_bounds(q_bits: Array, k_bits_bp: Array, lengths: Array, *,
                      d: int, page: int) -> Array:
    """Pure-jnp twin of :func:`paged_page_scores` on GATHERED bit-planes.

    Used by the non-kernel serving paths (which gather pages into rows
    anyway) and as the reference for kernel tests.

    Args:
      q_bits: [B, Hk, G, W] uint32 query bits.
      k_bits_bp: [B, Hk, W, T] uint32 gathered bit-planes, T = nb*page
        in logical order.
      lengths: [B] int32 valid context length per slot.

    Returns: [B, Hk, nb] int32 upper-bound page scores.
    """
    b, hk, w, t = k_bits_bp.shape
    nb = t // page
    lengths = jnp.asarray(lengths, jnp.int32).reshape(b)
    pos = jnp.arange(t, dtype=jnp.int32).reshape(1, nb, page)
    valid = pos < lengths[:, None, None]                  # [B, nb, page]
    kp = k_bits_bp.reshape(b, hk, w, nb, page)
    kp = jnp.where(valid[:, None, None], kp, jnp.uint32(0))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = jnp.right_shift(kp[..., None, :], shifts[:, None]) & jnp.uint32(1)
    cnt = jnp.sum(bits.astype(jnp.int32), axis=-1)        # [B,Hk,W,nb,32]
    cnt = jnp.moveaxis(cnt, 3, 2).reshape(b, hk, nb, w * 32)
    nv = jnp.clip(lengths[:, None] -
                  jnp.arange(nb, dtype=jnp.int32) * page, 0, page)
    nv = nv[:, None, None, :, None]                       # [B,1,1,nb,1]
    qbit = jnp.right_shift(q_bits[..., None], shifts) & jnp.uint32(1)
    qbit = qbit.reshape(b, hk, -1, w * 32)                # [B,Hk,G,W*32]
    match = jnp.where(qbit[:, :, :, None] == jnp.uint32(1),
                      cnt[:, :, None] > 0, cnt[:, :, None] < nv)
    live = jnp.arange(w * 32, dtype=jnp.int32) < d
    match = jnp.logical_and(match, live)                  # [B,Hk,G,nb,d']
    ub = 2 * jnp.sum(match.astype(jnp.int32), axis=-1) - d
    return jnp.max(ub, axis=2)                            # [B, Hk, nb]
