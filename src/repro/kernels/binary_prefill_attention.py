"""Pallas TPU kernel: HAD prefill attention (causal, top-N, packed bits).

Flash-attention-shaped two-pass streaming per query block (DESIGN.md §3):

  pass 0 over key blocks: Hamming scores -> per-row histogram
                          -> exact top-N threshold at the last key block
  pass 1 over key blocks: threshold-masked exp accumulation (num/den)

Unlike float flash attention there is no running-max rescaling: binary
scores are bounded by d, so exp(scale*(s - d)) <= 1 is always stable —
another simplification bought by binarization.

Causal masking is positional; key blocks entirely in the future of the
query block are skipped via pl.when (no VPU work issued).

Grid: (B*H, S/block_q, 2, T/block_t); GQA is handled by the K/V index maps
(query head h reads KV head h // group_size).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

from repro.kernels.binary_decode_attention import _threshold


def _scores_qk(q: Array, k: Array, d: int) -> Array:
    """[bq, W] x [W, bt] -> [bq, bt] int32."""
    ham = jnp.zeros((q.shape[0], k.shape[1]), dtype=jnp.int32)
    for wi in range(q.shape[1]):
        x = jnp.bitwise_xor(q[:, wi][:, None], k[wi, :][None, :])
        ham += jax.lax.population_count(x).astype(jnp.int32)
    return d - 2 * ham


def _prefill_kernel(len_ref, nsel_ref, scale_ref, qoff_ref, qlen_ref,
                    q_ref, k_ref, v_ref, o_ref,
                    hist_ref, thr_ref, num_ref, den_ref, *, d: int,
                    block_q: int, block_t: int, causal: bool):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ph = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = qoff_ref[bh] + qi * block_q
    # Skip query blocks made entirely of chunk padding (ragged serving:
    # only qlen_ref[bh] of this row's queries are real) and key blocks
    # strictly in the future of the whole query block.
    block_live = qi * block_q < qlen_ref[bh]
    if causal:
        block_live = jnp.logical_and(block_live,
                                     ki * block_t <= q_start + block_q - 1)

    @pl.when((ph == 0) & (ki == 0))
    def _init_hist():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(block_live)
    def _work():
        q = q_ref[0]                     # [bq, W]
        k = k_ref[0]                     # [W, bt]
        s = _scores_qk(q, k, d)          # [bq, bt]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ki * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < len_ref[bh]
        if causal:
            valid = jnp.logical_and(valid, kpos <= qpos)

        @pl.when(ph == 0)
        def _accum_hist():
            # one level per iteration: a [bq, bt, d+1] compare would not
            # fit VMEM at serving block sizes
            lv = jnp.where(valid, (s + d) >> 1, -1)         # [bq, bt]
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, d + 1), 1)

            def level(l, cc):
                col = jnp.sum((lv >= l).astype(jnp.int32), axis=1,
                              keepdims=True)
                return cc + jnp.where(lane == l, col, 0)

            hist_ref[...] += jax.lax.fori_loop(
                0, d + 1, level, jnp.zeros(hist_ref.shape, jnp.int32))

        @pl.when(ph == 1)
        def _accum_softmax():
            keep = jnp.logical_and(s >= thr_ref[...], valid)
            e = jnp.where(keep,
                          jnp.exp(scale_ref[0] * (s - d).astype(jnp.float32)),
                          0.0)
            num_ref[...] += jax.lax.dot_general(
                e, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            den_ref[...] += jnp.sum(e, axis=-1, keepdims=True)

    @pl.when((ph == 0) & (ki == nk - 1))
    def _finalize_threshold():
        thr_ref[...] = _threshold(hist_ref[...], nsel_ref[0], d)
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    @pl.when((ph == 1) & (ki == nk - 1))
    def _write_out():
        o_ref[0] = num_ref[...] / jnp.maximum(den_ref[...], 1e-30)


def prefill_attention(q_bits: Array, k_bits_planes: Array, v: Array, *,
                      d: int, nsel: Array, scale: Array, kv_length: Array,
                      q_offset: Array, group_size: int, n_kv_heads: int,
                      q_length: Array | None = None,
                      causal: bool = True,
                      block_q: int = 256, block_t: int = 512,
                      interpret: bool = True) -> Array:
    """Fused HAD prefill attention.

    Args:
      q_bits: [BH, S, W] uint32 query bits, flattened in [B, Hk, G] leading
        order (query head row b*Hk*G + hk*G + g reads KV row b*Hk + hk).
      k_bits_planes: [BHk, W, T] uint32 K bit-planes.
      v: [BHk, T, Dv] V cache/projections.
      nsel, scale: [1]-shaped runtime scalars.
      kv_length, q_offset: [BH] int32 per-query-row valid cache length and
        position offset — ragged batches get different values per slot.
      q_length: optional [BH] int32 per-row count of valid (non-padding)
        queries; query blocks entirely past a row's count are skipped
        (their outputs are zeros). None means all S queries are real.
      group_size: query heads per KV head (GQA G).
      n_kv_heads: KV heads per batch element (for the GQA index map).

    Returns: [BH, S, Dv] float32. Rows of a partially-valid query block
    beyond q_length are computed but garbage — callers discard them.
    """
    bh, s, w = q_bits.shape
    bhk, w2, t = k_bits_planes.shape
    _, t2, dv = v.shape
    assert w == w2 and t == t2 and bh == bhk * group_size
    assert kv_length.shape == (bh,) and q_offset.shape == (bh,)
    if q_length is None:
        q_length = jnp.full((bh,), s, jnp.int32)
    assert q_length.shape == (bh,)
    bq, bt = min(block_q, s), min(block_t, t)
    assert s % bq == 0 and t % bt == 0
    kernel = functools.partial(_prefill_kernel, d=d, block_q=bq, block_t=bt,
                               causal=causal)
    g, hk = group_size, n_kv_heads

    def kv_row(b):
        # flat query row b = bi*(hk*g) + hki*g + gi  ->  KV row bi*hk + hki
        return (b // (hk * g)) * hk + (b % (hk * g)) // g

    return pl.pallas_call(
        kernel,
        grid=(bh, s // bq, 2, t // bt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_length [BH]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # nsel [1]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scale [1]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # q_offset [BH]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # q_length [BH]
            pl.BlockSpec((1, bq, w), lambda b, qi, ph, ki: (b, qi, 0)),
            pl.BlockSpec((1, w, bt), lambda b, qi, ph, ki: (kv_row(b), 0, ki)),
            pl.BlockSpec((1, bt, dv), lambda b, qi, ph, ki: (kv_row(b), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, qi, ph, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, d + 1), jnp.int32),
            pltpu.VMEM((bq, 1), jnp.int32),
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(kv_length, nsel, scale, q_offset, q_length.astype(jnp.int32),
      q_bits, k_bits_planes, v)
