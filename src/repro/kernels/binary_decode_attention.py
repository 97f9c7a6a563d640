"""Pallas TPU kernel: fused HAD decode attention (one new token).

Per (batch, kv-head) group: integer Hamming scores against the packed-bit K
cache, exact top-N via the histogram threshold (DESIGN.md §3), and the
threshold-masked softmax·V accumulation — all in one kernel, streaming the
K/V cache through VMEM in two passes:

  pass 0: scores -> cumulative score-level histogram (per query row, the
          count of keys at or above each of the d+1 levels)
          -> exact top-N threshold at the last block
  pass 1: scores recomputed (cheap: XOR+popcount), mask = score >= threshold,
          stable exp accumulation of numerator [G, Dv] and denominator [G]

Bytes moved: K cache is uint32 bit-planes (16x smaller than bf16), read in
both passes; V's BlockSpec follows the block index in both passes, so V is
read twice here (the paged kernel reads each page's K and V once); scores
are never materialized in HBM. The histogram makes top-N a streaming
O(d)-state operation — no sort, no gather, no O(T) score buffer.

Grid: (B*Hk, 2, T/block_t) — sequential on TPU, so VMEM scratch carries the
histogram/threshold/accumulators across passes within each (batch, kv-head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _scores(q: Array, k: Array, d: int) -> Array:
    """[G, W] x [W, bt] -> [G, bt] int32."""
    ham = jnp.zeros((q.shape[0], k.shape[1]), dtype=jnp.int32)
    for wi in range(q.shape[1]):
        x = jnp.bitwise_xor(q[:, wi][:, None], k[wi, :][None, :])
        ham += jax.lax.population_count(x).astype(jnp.int32)
    return d - 2 * ham


def _at_or_above(s: Array, valid: Array, d: int) -> Array:
    """Per-row counts of valid scores at or above each level.

    s, valid: [R, bt]; returns [R, d+1] int32 with column l counting the
    valid keys whose level (s + d) / 2 is >= l. Counts add across blocks,
    and the threshold reads them directly, so no reversed scan is needed.
    Builds an [R, d+1, bt] compare, keys on lanes, summed across lanes:
    for small R * bt (decode blocks).
    """
    lv = jnp.where(valid, (s + d) >> 1, -1)                  # [R, bt]
    ge = lv[:, None, :] >= jax.lax.broadcasted_iota(jnp.int32,
                                                    (1, d + 1, 1), 1)
    return jnp.sum(ge.astype(jnp.int32), axis=2)


def _threshold(cc: Array, nsel: Array, d: int) -> Array:
    """Exact top-N threshold score per row from the at-or-above counts.

    cc: [G, d+1] counts (column l = count(level >= l)); returns [G, 1]
    int32 threshold scores such that keeping score >= t keeps
    >= min(nsel, total) entries (ties included).
    """
    total = cc[:, :1]
    n_eff = jnp.minimum(nsel.astype(jnp.int32), total)
    levels = jax.lax.broadcasted_iota(jnp.int32, cc.shape, 1)
    idx = jnp.max(jnp.where(cc >= n_eff, levels, -1), axis=-1, keepdims=True)
    idx = jnp.maximum(idx, 0)
    return 2 * idx - d


def _decode_kernel(len_ref, nsel_ref, scale_ref, q_ref, k_ref, v_ref, o_ref,
                   hist_ref, thr_ref, num_ref, den_ref, blkmax_ref,
                   thrmin_ref, *,
                   d: int, block_t: int, block_skip: bool):
    bh = pl.program_id(0)
    ph = pl.program_id(1)
    i = pl.program_id(2)
    nb = pl.num_programs(2)

    q = q_ref[0]            # [G, W]

    def scores_valid():
        k = k_ref[0]            # [W, bt]
        s = _scores(q, k, d)    # [G, bt] int32
        pos = i * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return s, pos < len_ref[bh]

    @pl.when((ph == 0) & (i == 0))
    def _init_hist():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(ph == 0)
    def _accum_hist():
        s, valid = scores_valid()
        hist_ref[...] += _at_or_above(s, valid, d)
        if block_skip:
            # per-block max score across all G rows: pass 2 skips blocks
            # whose best score misses every row's threshold — top-N then
            # saves actual V-read BYTES, not just flops (beyond-paper;
            # EXPERIMENTS.md §Perf). At N/T = 1-12% most blocks skip.
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
        # scores <= d, so exp(scale*(s-d)) <= 1: stable without row max.
        e = jnp.where(keep,
                      jnp.exp(scale_ref[0] * (s - d).astype(jnp.float32)),
                      0.0)
        num_ref[...] += jax.lax.dot_general(
            e, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        den_ref[...] += jnp.sum(e, axis=-1, keepdims=True)

    @pl.when((ph == 1) & (i == nb - 1))
    def _write_out():
        o_ref[0] = num_ref[...] / jnp.maximum(den_ref[...], 1e-30)


def decode_attention(q_bits: Array, k_bits_planes: Array, v: Array, *,
                     d: int, nsel: Array, scale: Array, lengths: Array,
                     block_t: int = 512, interpret: bool = True,
                     block_skip: bool = True) -> Array:
    """Fused HAD decode attention.

    Args:
      q_bits: [BHk, G, W] uint32 — new-token query bits, grouped per KV head.
      k_bits_planes: [BHk, W, T] uint32 — K cache, bit-plane layout.
      v: [BHk, T, Dv] — V cache (any float dtype).
      d: head dimension (bits).
      nsel: [1] int32 — top-N.
      scale: [1] float32 — sigma_q * sigma_k / sqrt(d_k) logit scale.
      lengths: [BHk] int32 — valid cache length per row.
      block_t: K/V block along the sequence axis (VMEM tile).

    Returns: [BHk, G, Dv] float32 attention outputs.
    """
    bhk, g, w = q_bits.shape
    _, w2, t = k_bits_planes.shape
    _, t2, dv = v.shape
    assert w == w2 and t == t2
    bt = min(block_t, t)
    assert t % bt == 0, (t, bt)
    kernel = functools.partial(_decode_kernel, d=d, block_t=bt,
                               block_skip=block_skip)
    return pl.pallas_call(
        kernel,
        grid=(bhk, 2, t // bt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths [BHk]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # nsel [1]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scale [1]
            pl.BlockSpec((1, g, w), lambda bh, ph, i: (bh, 0, 0)),
            pl.BlockSpec((1, w, bt), lambda bh, ph, i: (bh, 0, i)),
            pl.BlockSpec((1, bt, dv), lambda bh, ph, i: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, dv), lambda bh, ph, i: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bhk, g, dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, d + 1), jnp.int32),   # at-or-above counts
            pltpu.VMEM((g, 1), jnp.int32),       # threshold
            pltpu.VMEM((g, dv), jnp.float32),    # numerator
            pltpu.VMEM((g, 1), jnp.float32),     # denominator
            pltpu.SMEM((t // bt,), jnp.int32),   # per-block max (skip list)
            pltpu.SMEM((1,), jnp.int32),         # min threshold over rows
        ],
        interpret=interpret,
    )(lengths, nsel, scale, q_bits, k_bits_planes, v)
