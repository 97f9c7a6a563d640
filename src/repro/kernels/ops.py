"""jit'd public wrappers over the Pallas kernels.

Handle layout (row-major <-> bit-plane), GQA grouping, padding to block
multiples, and the interpret-mode switch: the kernels run compiled on a
TPU, and in interpret mode (Python-executed bodies) on the CPU backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hamming
from repro.kernels import binary_decode_attention as _dec
from repro.kernels import binary_page_score as _pscore
from repro.kernels import binary_paged_decode_attention as _pdec
from repro.kernels import binary_prefill_attention as _pre
from repro.kernels import hamming_score as _hs

Array = jax.Array


def resolve_interpret(interpret: bool | None) -> bool:
    """A wrapper's `interpret` argument. None means interpret mode on the
    CPU backend only; asking for it on a TPU raises instead of running
    the Python interpreter in place of the compiled kernel."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    if interpret and jax.default_backend() == "tpu":
        raise ValueError("Pallas interpret mode was requested on a TPU; "
                         "the kernels run compiled there")
    return interpret


def to_bitplanes(k_bits: Array) -> Array:
    """Row-major packed bits [..., T, W] -> bit-plane layout [..., W, T]."""
    return jnp.swapaxes(k_bits, -1, -2)


def _pad_to(x: Array, axis: int, mult: int) -> Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("d", "block_m", "block_n",
                                             "method", "interpret"))
def hamming_scores(q_bits: Array, k_bits: Array, d: int, *,
                   block_m: int = 128, block_n: int = 128,
                   method: str = "xor",
                   interpret: bool | None = None) -> Array:
    """Binary scores for row-major packed bits with arbitrary leading dims.

    q_bits: [..., M, W]; k_bits: [..., N, W] -> [..., M, N] int32.
    """
    interpret = resolve_interpret(interpret)
    lead = q_bits.shape[:-2]
    m, w = q_bits.shape[-2:]
    n = k_bits.shape[-2]
    qf = q_bits.reshape(-1, m, w)
    kf = to_bitplanes(k_bits.reshape(-1, n, w))
    bm = min(block_m, m)
    bn = min(block_n, n)
    qf = _pad_to(qf, 1, bm)
    kf = _pad_to(kf, 2, bn)

    fn = functools.partial(_hs.hamming_score, d=d, block_m=bm, block_n=bn,
                           method=method, interpret=interpret)
    out = jax.vmap(fn)(qf, kf)
    return out[:, :m, :n].reshape(*lead, m, n)


@functools.partial(jax.jit, static_argnames=("d", "block_t", "interpret",
                                             "bitplanes"))
def decode_attention(q_bits: Array, k_bits: Array, v: Array, *, d: int,
                     nsel: Array | int, scale: Array | float,
                     lengths: Array, block_t: int = 512,
                     interpret: bool | None = None,
                     bitplanes: bool = False) -> Array:
    """HAD decode attention for one new token.

    q_bits: [B, H, W] uint32; k_bits: [B, Hk, T, W] (row-major) or
    [B, Hk, W, T] when bitplanes=True; v: [B, Hk, T, Dv];
    lengths: [B] int32 valid cache lengths. Returns [B, H, Dv] f32.
    """
    interpret = resolve_interpret(interpret)
    b, h, w = q_bits.shape
    if bitplanes:
        _, hk, w2, t = k_bits.shape
        kf = k_bits.reshape(b * hk, w, t)
    else:
        _, hk, t, w2 = k_bits.shape
        kf = to_bitplanes(k_bits).reshape(b * hk, w, t)
    assert w == w2
    g = h // hk
    dv = v.shape[-1]
    qf = q_bits.reshape(b, hk, g, w).reshape(b * hk, g, w)
    vf = v.reshape(b * hk, t, dv)
    bt = min(block_t, t)
    kf = _pad_to(kf, 2, bt)
    vf = _pad_to(vf, 1, bt)
    len_f = jnp.broadcast_to(lengths[:, None], (b, hk)).reshape(-1)
    out = _dec.decode_attention(
        qf, kf, vf, d=d,
        nsel=jnp.asarray([nsel], dtype=jnp.int32).reshape(1),
        scale=jnp.asarray([scale], dtype=jnp.float32).reshape(1),
        lengths=len_f.astype(jnp.int32), block_t=bt, interpret=interpret)
    return out.reshape(b, h, dv)


def _slot_tables(block_tables: Array, lengths: Array,
                 page: int) -> tuple[Array, Array]:
    """Per-slot [B, nb] table + [B] lengths -> the table clamped in range
    and per-block valid counts [B, nb]."""
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    nb = bt.shape[1]
    counts = jnp.clip(jnp.asarray(lengths, jnp.int32)[:, None] -
                      jnp.arange(nb, dtype=jnp.int32)[None] * page, 0, page)
    return bt, counts.astype(jnp.int32)


def select_pages(scores: Array, block_tables: Array, lengths: Array, *,
                 page: int, n_sel: int) -> tuple[Array, Array, Array]:
    """Phase-1 -> phase-2 handoff: keep each row's top-n_sel pages, with
    the frontier (tail) page ALWAYS among them.

    scores: [R, nb] per-page scores (higher = keep); block_tables:
    [R, nb] int32 physical ids; lengths: [R] int32 valid context
    lengths. n_sel is STATIC (clamped to nb). Returns compacted
    (tables [R, n_sel], counts [R, n_sel], logical [R, n_sel]) with
    blocks in ascending logical order, so phase 2 accumulates in the
    same order as the dense walk.

    Invariants: the frontier block (holding token lengths-1) is always
    selected (its score is forced to +BIG — the just-written token is
    never dropped); invalid blocks (past the frontier) are forced to
    -BIG, and any that still get picked (fewer resident blocks than
    n_sel) keep count 0 and a clamped in-range page id — compacted
    tables never contain the -1 / out-of-bounds drop sentinel.

    Rows are independent, so under tensor-parallel serving the R =
    B x local-kv-heads rows of each shard compact their own tables with
    no collective — selection is per (slot, LOCAL kv-head) by design.
    """
    r, nb = scores.shape
    n_sel = min(n_sel, nb)
    blocks = jnp.arange(nb, dtype=jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    frontier = jnp.maximum(lengths - 1, 0) // page
    big = jnp.int32(jnp.iinfo(jnp.int32).max // 4)
    s = jnp.where(blocks[None] * page < lengths[:, None],
                  scores.astype(jnp.int32), -big)
    s = jnp.where(blocks[None] == frontier[:, None], big, s)
    _, idx = jax.lax.top_k(s, n_sel)        # ties -> lowest logical block
    idx = jnp.sort(idx, axis=1)             # ascending logical order
    counts = jnp.clip(lengths[:, None] - idx * page, 0, page)
    tables = jnp.maximum(jnp.take_along_axis(
        jnp.asarray(block_tables, jnp.int32), idx, axis=1), 0)
    return tables, counts.astype(jnp.int32), idx


@functools.partial(jax.jit, static_argnames=("d", "page_topn", "interpret"))
def paged_decode_attention(q_bits: Array, k_pool: Array, v_pool: Array,
                           block_tables: Array, *, d: int,
                           nsel: Array | int, scale: Array | float,
                           lengths: Array, page_topn: int | None = None,
                           interpret: bool | None = None) -> Array:
    """HAD decode attention for one new token against PAGED K/V pools.

    q_bits: [B, H, W] uint32; k_pool: [n_pages, Hk, W, page] bit-planes;
    v_pool: [n_pages, Hk, page, Dv]; block_tables: [B, max_blocks] int32
    (-1/garbage entries past each row's valid length are clamped — they
    are masked by per-block counts); lengths: [B] int32 valid cache
    lengths. Returns [B, H, Dv] f32. Block tables and lengths are
    traced: new contents never recompile.

    The kernel walks each (slot, kv-head) row in blocks of
    `pages_per_block(page)` listed pages (32 at page 16, ~512 tokens a
    grid step) and stops after the row's last resident page, so a
    decode step costs what the rows hold, not `max_blocks`. Each page's
    K is read once, in the histogram pass (the second pass reuses its
    scores), and its V once, in the second.

    page_topn (STATIC) switches on two-phase page-sparse decode:
    phase 1 scores every resident page per (slot, kv-head) with the
    popcount upper-bound kernel, phase 2 runs the decode kernel over a
    COMPACTED per-row block table of the top-page_topn pages (frontier
    always included), so V gathers drop from O(context) to
    O(page_topn * page). At page_topn >= max_blocks the dense walk runs
    unchanged; at page_topn >= resident pages the result is
    bit-identical to dense (all resident pages selected, same order,
    grouped into the same blocks).

    Head-shardable by construction: every row of the flattened
    (slot, kv-head) grid — scoring, `select_pages` compaction, and the
    decode walk — depends only on its own kv head's pool slice and the
    replicated block table. Tensor-parallel serving calls this unchanged
    inside shard_map on local head slices (q_bits [B, H/tp, W], pools
    sharded on their kv-head axis) with zero cross-device traffic; the
    group structure must survive the split, i.e. Hk % tp == 0 (enforced
    by serve/validate.py) so h/hk stays the global group size g.
    """
    interpret = resolve_interpret(interpret)
    b, h, w = q_bits.shape
    _, hk, w2, page = k_pool.shape
    assert w == w2
    assert h % hk == 0, (h, hk)   # whole GQA groups (global or TP-local)
    g = h // hk
    dv = v_pool.shape[-1]
    nb = block_tables.shape[1]
    qf = q_bits.reshape(b, hk, g, w).reshape(b * hk, g, w)
    # dense: one table row per slot, shared by its kv heads; top-N: one
    # compacted row per (slot, kv-head)
    tables, counts = _slot_tables(block_tables, lengths, page)
    if page_topn is not None and page_topn < nb:
        scores = _pscore.paged_page_scores(qf, k_pool, tables, counts,
                                           d=d, n_kv_heads=hk,
                                           interpret=interpret)
        tables, counts, _ = select_pages(
            scores, jnp.repeat(tables, hk, axis=0),
            jnp.repeat(jnp.asarray(lengths, jnp.int32), hk),
            page=page, n_sel=page_topn)
    out = _pdec.paged_decode_attention(
        qf, k_pool, v_pool, tables,
        d=d, nsel=jnp.asarray([nsel], dtype=jnp.int32).reshape(1),
        scale=jnp.asarray([scale], dtype=jnp.float32).reshape(1),
        counts=counts, n_kv_heads=hk,
        interpret=interpret)
    return out.reshape(b, h, dv)


@functools.partial(jax.jit, static_argnames=("d", "causal", "block_q",
                                             "block_t", "interpret"))
def prefill_attention(q_bits: Array, k_bits: Array, v: Array, *, d: int,
                      nsel: Array | int, scale: Array | float,
                      kv_length: Array | int, q_offset: Array | int = 0,
                      q_length: Array | int | None = None,
                      causal: bool = True, block_q: int = 256,
                      block_t: int = 512,
                      interpret: bool | None = None) -> Array:
    """HAD prefill attention over a query chunk.

    q_bits: [B, H, S, W]; k_bits: [B, Hk, T, W] row-major; v: [B, Hk, T, Dv].
    kv_length / q_offset are scalars (uniform batch) or [B] int32 vectors
    with per-slot cache lengths / position offsets (ragged batch).
    q_length (optional, same scalar/vector convention) is the per-slot
    count of valid queries in a padded chunk: fully-padded query blocks
    are skipped in the kernel (zero output rows).
    Returns [B, H, S, Dv] float32.
    """
    interpret = resolve_interpret(interpret)
    b, h, s, w = q_bits.shape
    _, hk, t, w2 = k_bits.shape
    assert w == w2
    g = h // hk
    dv = v.shape[-1]
    bq = min(block_q, s)
    bt = min(block_t, t)
    qf = q_bits.reshape(b * h, s, w)
    qf = _pad_to(qf, 1, bq)
    kf = _pad_to(to_bitplanes(k_bits).reshape(b * hk, w, t), 2, bt)
    vf = _pad_to(v.reshape(b * hk, t, dv), 1, bt)
    # flat query row = bi*H + head -> repeat each per-batch scalar H times
    kv_len = jnp.broadcast_to(jnp.asarray(kv_length, jnp.int32), (b,))
    q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    q_len = jnp.broadcast_to(jnp.asarray(s if q_length is None else q_length,
                                         jnp.int32), (b,))
    out = _pre.prefill_attention(
        qf, kf, vf, d=d,
        nsel=jnp.asarray([nsel], dtype=jnp.int32).reshape(1),
        scale=jnp.asarray([scale], dtype=jnp.float32).reshape(1),
        kv_length=jnp.repeat(kv_len, h),
        q_offset=jnp.repeat(q_off, h),
        q_length=jnp.repeat(q_len, h),
        group_size=g, n_kv_heads=hk, causal=causal, block_q=bq, block_t=bt,
        interpret=interpret)
    return out[:, :s].reshape(b, h, s, dv)
