"""Per-kernel allclose tests vs the pure-jnp oracles (interpret mode).

Shape/dtype sweeps per the deliverables: every Pallas kernel is checked
against ref.py across head dims (incl. non-multiples of 32), GQA group
sizes, sequence lengths that do/don't divide the block size, and V dtypes.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import hamming
from repro.kernels import binary_paged_decode_attention as PDEC
from repro.kernels import ops, ref


def _bits(shape_d, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=shape_d).astype(np.float32)
    return hamming.pack_bits(jnp.asarray(x))


# ---------------------------------------------------------------------------
# hamming_score
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [32, 64, 128, 112, 80])
@pytest.mark.parametrize("m,n", [(8, 16), (16, 8)])
@pytest.mark.parametrize("method", ["xor", "int8"])
def test_hamming_score_matches_ref(d, m, n, method):
    qb = _bits((m, d), d + m)
    kb = _bits((n, d), d + n + 1)
    got = ops.hamming_scores(qb, kb, d, block_m=8, block_n=8, method=method,
                             interpret=True)
    want = ref.hamming_score_ref(qb, kb, d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hamming_score_batched_and_padded():
    d = 64
    qb = _bits((2, 3, 5, d), 0)   # M=5 not divisible by block
    kb = _bits((2, 3, 7, d), 1)
    got = ops.hamming_scores(qb, kb, d, block_m=4, block_n=4, interpret=True)
    want = ref.hamming_score_ref(qb, kb, d)
    assert got.shape == (2, 3, 5, 7)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(1, 4), st.integers(1, 24), st.integers(1, 24),
       st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_hamming_score_property(dw, m, n, seed):
    d = dw * 32
    qb = _bits((m, d), seed)
    kb = _bits((n, d), seed + 1)
    got = ops.hamming_scores(qb, kb, d, block_m=8, block_n=8, interpret=True)
    want = ref.hamming_score_ref(qb, kb, d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# binary_decode_attention
# ---------------------------------------------------------------------------

def _decode_case(b, h, hk, t, d, dv, nsel, lengths, seed=0, vdtype=jnp.float32,
                 block_t=32):
    qb = _bits((b, h, d), seed)
    kb = _bits((b, hk, t, d), seed + 1)
    rng = np.random.default_rng(seed + 2)
    v = jnp.asarray(rng.normal(size=(b, hk, t, dv)).astype(np.float32),
                    dtype=vdtype)
    scale = 1.0 / np.sqrt(d)
    lengths = jnp.asarray(lengths, dtype=jnp.int32)
    got = ops.decode_attention(qb, kb, v, d=d, nsel=nsel, scale=scale,
                               lengths=lengths, block_t=block_t,
                               interpret=True)
    g = h // hk
    qg = qb.reshape(b, hk, g, -1).reshape(b * hk, g, -1)
    kf = kb.reshape(b * hk, t, -1)
    vf = v.reshape(b * hk, t, dv)
    lens_f = jnp.broadcast_to(lengths[:, None], (b, hk)).reshape(-1)
    want = ref.decode_attention_ref(qg, kf, vf, d=d, nsel=nsel, scale=scale,
                                    lengths=lens_f)
    want = want.reshape(b, h, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d", [32, 64, 128])
@pytest.mark.parametrize("hk", [1, 2])
def test_decode_attention_basic(d, hk):
    _decode_case(b=2, h=4, hk=hk, t=96, d=d, dv=16, nsel=10,
                 lengths=[96, 96], seed=d)


def test_decode_attention_ragged_lengths():
    _decode_case(b=3, h=2, hk=1, t=64, d=32, dv=8, nsel=5,
                 lengths=[64, 17, 1], seed=7)


def test_decode_attention_padded_t():
    # t=50 not a multiple of block_t=32 -> ops pads; lengths mask the tail
    _decode_case(b=1, h=2, hk=2, t=50, d=64, dv=12, nsel=8,
                 lengths=[50], seed=9)


def test_decode_attention_bf16_values():
    _decode_case(b=1, h=2, hk=1, t=64, d=64, dv=16, nsel=6, lengths=[64],
                 seed=11, vdtype=jnp.bfloat16)


def test_decode_attention_n_exceeds_length():
    _decode_case(b=1, h=1, hk=1, t=32, d=32, dv=4, nsel=1000, lengths=[20],
                 seed=13)


@given(st.integers(1, 3), st.integers(1, 2), st.integers(2, 5),
       st.integers(1, 64), st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_decode_attention_property(b, hk, g, nsel, seed):
    t = 48
    _decode_case(b=b, h=hk * g, hk=hk, t=t, d=32, dv=8, nsel=nsel,
                 lengths=list(np.random.default_rng(seed).integers(1, t + 1, b)),
                 seed=seed, block_t=16)


# ---------------------------------------------------------------------------
# binary_prefill_attention
# ---------------------------------------------------------------------------

def _prefill_case(b, h, hk, s, t, d, dv, nsel, kv_length, q_offset=0,
                  causal=True, seed=0, block_q=16, block_t=32,
                  vdtype=jnp.float32):
    qb = _bits((b, h, s, d), seed)
    kb = _bits((b, hk, t, d), seed + 1)
    rng = np.random.default_rng(seed + 2)
    v = jnp.asarray(rng.normal(size=(b, hk, t, dv)).astype(np.float32),
                    dtype=vdtype)
    scale = 1.0 / np.sqrt(d)
    got = ops.prefill_attention(qb, kb, v, d=d, nsel=nsel, scale=scale,
                                kv_length=kv_length, q_offset=q_offset,
                                causal=causal, block_q=block_q,
                                block_t=block_t, interpret=True)
    g = h // hk
    want = ref.prefill_attention_ref(
        qb.reshape(b * h, s, -1), kb.reshape(b * hk, t, -1),
        v.reshape(b * hk, t, dv), d=d, nsel=nsel, scale=scale,
        kv_length=kv_length, q_offset=q_offset, group_size=g, causal=causal)
    want = want.reshape(b, h, s, dv)
    got_np, want_np = np.asarray(got), np.asarray(want, np.float32)
    if causal and q_offset == 0:
        # rows with no valid key can't occur (self always valid)
        pass
    np.testing.assert_allclose(got_np, want_np, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d", [32, 64, 128])
def test_prefill_causal_basic(d):
    _prefill_case(b=1, h=2, hk=2, s=64, t=64, d=d, dv=16, nsel=8,
                  kv_length=64, seed=d)


def test_prefill_gqa_grouping_batch_gt1():
    # regression: GQA KV index map with batch > 1
    _prefill_case(b=2, h=4, hk=2, s=32, t=32, d=32, dv=8, nsel=6,
                  kv_length=32, seed=3)


def test_prefill_non_causal():
    _prefill_case(b=1, h=2, hk=1, s=32, t=48, d=64, dv=8, nsel=12,
                  kv_length=48, causal=False, seed=5)


def test_prefill_q_offset_chunked_equals_full():
    """Prefill in two chunks (with q_offset) == one-shot prefill."""
    b, h, hk, s, d, dv, nsel = 1, 2, 1, 64, 32, 8, 10
    qb = _bits((b, h, s, d), 21)
    kb = _bits((b, hk, s, d), 22)
    rng = np.random.default_rng(23)
    v = jnp.asarray(rng.normal(size=(b, hk, s, dv)).astype(np.float32))
    scale = 1.0 / np.sqrt(d)
    full = ops.prefill_attention(qb, kb, v, d=d, nsel=nsel, scale=scale,
                                 kv_length=s, block_q=16, block_t=16,
                                 interpret=True)
    half = s // 2
    out1 = ops.prefill_attention(qb[:, :, :half], kb, v, d=d, nsel=nsel,
                                 scale=scale, kv_length=s, q_offset=0,
                                 block_q=16, block_t=16, interpret=True)
    out2 = ops.prefill_attention(qb[:, :, half:], kb, v, d=d, nsel=nsel,
                                 scale=scale, kv_length=s, q_offset=half,
                                 block_q=16, block_t=16, interpret=True)
    got = jnp.concatenate([out1, out2], axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_prefill_q_length_skips_padded_rows():
    """Ragged q_length (padded serving chunks): valid rows match the
    oracle, rows of fully-dead query blocks are zero-skipped."""
    b, h, hk, s, t, d, dv, nsel = 2, 2, 1, 32, 32, 32, 8, 6
    qb = _bits((b, h, s, d), 41)
    kb = _bits((b, hk, t, d), 42)
    rng = np.random.default_rng(43)
    v = jnp.asarray(rng.normal(size=(b, hk, t, dv)).astype(np.float32))
    scale = 1.0 / np.sqrt(d)
    q_len = jnp.asarray([20, 0], jnp.int32)        # row 1: all padding
    kv_len = jnp.asarray([20, 9], jnp.int32)
    got = ops.prefill_attention(qb, kb, v, d=d, nsel=nsel, scale=scale,
                                kv_length=kv_len, q_offset=0,
                                q_length=q_len, block_q=16, block_t=16,
                                interpret=True)
    want = ref.prefill_attention_ref(
        qb.reshape(b * h, s, -1), kb.reshape(b * hk, t, -1),
        v.reshape(b * hk, t, dv), d=d, nsel=nsel, scale=scale,
        kv_length=jnp.repeat(kv_len, h), q_offset=jnp.zeros(b * h, jnp.int32),
        q_length=jnp.repeat(q_len, h), group_size=h // hk)
    want = want.reshape(b, h, s, dv)
    got_np, want_np = np.asarray(got), np.asarray(want, np.float32)
    # valid region pinned to the oracle
    np.testing.assert_allclose(got_np[0, :, :20], want_np[0, :, :20],
                               rtol=2e-5, atol=2e-5)
    # fully-dead query blocks are skipped outright -> zero outputs
    assert (got_np[1] == 0).all()                  # q_length 0: all skipped


def test_prefill_padded_s_and_t():
    _prefill_case(b=1, h=1, hk=1, s=24, t=40, d=32, dv=8, nsel=6,
                  kv_length=40, causal=False, seed=31, block_q=16, block_t=16)


def test_prefill_kv_length_masks_tail():
    _prefill_case(b=1, h=2, hk=1, s=16, t=64, d=32, dv=8, nsel=4,
                  kv_length=20, causal=False, seed=33)


def test_prefill_ragged_per_batch_lengths_and_offsets():
    """Per-batch kv_length/q_offset vectors == per-slot scalar calls."""
    b, h, hk, s, t, d, dv, nsel = 3, 2, 1, 16, 64, 32, 8, 6
    qb = _bits((b, h, s, d), 41)
    kb = _bits((b, hk, t, d), 42)
    rng = np.random.default_rng(43)
    v = jnp.asarray(rng.normal(size=(b, hk, t, dv)).astype(np.float32))
    scale = 1.0 / np.sqrt(d)
    kv_len = jnp.asarray([20, 48, 33], jnp.int32)
    q_off = jnp.asarray([4, 32, 17], jnp.int32)
    got = ops.prefill_attention(qb, kb, v, d=d, nsel=nsel, scale=scale,
                                kv_length=kv_len, q_offset=q_off,
                                block_q=16, block_t=16, interpret=True)
    for i in range(b):
        one = ops.prefill_attention(
            qb[i:i + 1], kb[i:i + 1], v[i:i + 1], d=d, nsel=nsel,
            scale=scale, kv_length=int(kv_len[i]), q_offset=int(q_off[i]),
            block_q=16, block_t=16, interpret=True)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(one[0]),
                                   rtol=2e-5, atol=2e-5)


def test_prefill_bf16_values():
    _prefill_case(b=1, h=2, hk=1, s=32, t=32, d=64, dv=16, nsel=8,
                  kv_length=32, seed=35, vdtype=jnp.bfloat16)


@given(st.integers(1, 2), st.integers(1, 2), st.integers(1, 3),
       st.integers(1, 40), st.integers(0, 999))
@settings(max_examples=8, deadline=None)
def test_prefill_property(b, hk, g, nsel, seed):
    _prefill_case(b=b, h=hk * g, hk=hk, s=32, t=32, d=32, dv=8, nsel=nsel,
                  kv_length=32, seed=seed)


def test_decode_agrees_with_prefill_last_row():
    """Decoding token T with cache == last row of a T-token prefill."""
    b, h, hk, t, d, dv, nsel = 1, 2, 1, 48, 32, 8, 10
    qb_all = _bits((b, h, t, d), 41)
    kb = _bits((b, hk, t, d), 42)
    rng = np.random.default_rng(43)
    v = jnp.asarray(rng.normal(size=(b, hk, t, dv)).astype(np.float32))
    scale = 1.0 / np.sqrt(d)
    pre = ops.prefill_attention(qb_all, kb, v, d=d, nsel=nsel, scale=scale,
                                kv_length=t, block_q=16, block_t=16,
                                interpret=True)
    dec = ops.decode_attention(qb_all[:, :, -1], kb, v, d=d, nsel=nsel,
                               scale=scale,
                               lengths=jnp.asarray([t], dtype=jnp.int32),
                               block_t=16, interpret=True)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(pre[:, :, -1]),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# binary_paged_decode_attention
# ---------------------------------------------------------------------------

def _paged_case(b, h, hk, nb, page, d, dv, nsel, lengths, n_pages,
                seed=0, vdtype=jnp.float32, nan_fill=False):
    """Scatter contiguous K/V into a shuffled page pool, then check the
    paged kernel against (a) the gather-based oracle and (b) the
    contiguous kernel on the same tokens — the latter bit-exactly, since
    pages stream in logical order and the contiguous kernel's block_t is
    the paged kernel's block of pages_per_block(page) pages.

    nan_fill: every pool page outside all rows' residency gets NaN V and
    all-ones K bits; the kernel must give the same bits as on the clean
    pool, so pages it does not fetch, or holds stale, contribute nothing.
    """
    qb, kb, v, k_pool, v_pool, bt = _make_pool(b, h, hk, nb, page, d, dv,
                                               n_pages, seed, vdtype)
    scale = 1.0 / np.sqrt(d)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = ops.paged_decode_attention(
        qb, k_pool, v_pool, bt, d=d, nsel=nsel, scale=scale,
        lengths=lengths, interpret=True)
    g = h // hk
    want = ref.paged_decode_attention_ref(
        qb.reshape(b, hk, g, -1), k_pool, v_pool, bt, d=d, nsel=nsel,
        scale=scale, lengths=lengths).reshape(b, h, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               rtol=2e-5, atol=2e-5)
    block_t = PDEC.pages_per_block(page) * page
    pad = [(0, 0), (0, 0), (0, -(nb * page) % block_t), (0, 0)]
    contig = ops.decode_attention(qb, jnp.pad(kb, pad), jnp.pad(v, pad), d=d,
                                  nsel=nsel, scale=scale, lengths=lengths,
                                  block_t=block_t, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(contig))
    if nan_fill:
        resident = np.zeros(n_pages, bool)
        for bi, n in enumerate(np.asarray(lengths)):
            resident[np.asarray(bt)[bi, :-(-int(n) // page)]] = True
        assert not resident.all(), "no page outside residency: test is void"
        k_nan = np.asarray(k_pool).copy()
        v_nan = np.asarray(v_pool).copy()
        k_nan[~resident] = np.uint32(0xFFFFFFFF)
        v_nan[~resident] = np.nan
        dirty = ops.paged_decode_attention(
            qb, jnp.asarray(k_nan), jnp.asarray(v_nan), bt, d=d, nsel=nsel,
            scale=scale, lengths=lengths, interpret=True)
        np.testing.assert_array_equal(np.asarray(dirty), np.asarray(got))


@pytest.mark.parametrize("d", [32, 64, 128])
@pytest.mark.parametrize("hk", [1, 2])
def test_paged_decode_basic(d, hk):
    _paged_case(b=2, h=4, hk=hk, nb=6, page=16, d=d, dv=16, nsel=10,
                lengths=[96, 96], n_pages=16, seed=d)


def test_paged_decode_ragged_lengths_and_garbage_tail():
    """Short rows leave trailing block-table entries unused; the wrapper
    clamps them and `lengths` masks whatever page they alias."""
    _paged_case(b=3, h=2, hk=1, nb=8, page=8, d=32, dv=8, nsel=5,
                lengths=[64, 17, 1], n_pages=24, seed=7)


@pytest.mark.parametrize("nan_fill", [False, True], ids=["clean", "nan"])
@pytest.mark.parametrize("lengths", [
    [512, 517, 1, 0],   # a block boundary, mid-page in block 2, 1, empty
    [640, 496, 33, 16],  # the full table, mid-block, mid-page, one page
], ids=["edges", "ragged"])
def test_paged_decode_blocked_walk(lengths, nan_fill):
    """Blocks of 32 pages at page 16 over a 40-page table (not a whole
    number of blocks): the walk ends at each row's last resident page."""
    _paged_case(b=4, h=4, hk=2, nb=40, page=16, d=32, dv=8, nsel=40,
                lengths=lengths, n_pages=4 * 40 + 6, seed=21,
                nan_fill=nan_fill)


def test_paged_decode_bf16_values():
    _paged_case(b=1, h=2, hk=1, nb=4, page=16, d=64, dv=16, nsel=6,
                lengths=[64], n_pages=6, seed=11, vdtype=jnp.bfloat16)


def test_paged_decode_n_exceeds_length():
    _paged_case(b=1, h=1, hk=1, nb=4, page=8, d=32, dv=4, nsel=1000,
                lengths=[20], seed=13, n_pages=4)


@given(st.integers(1, 3), st.integers(1, 2), st.integers(2, 4),
       st.integers(1, 48), st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_paged_decode_property(b, hk, g, nsel, seed):
    nb, page = 6, 8
    lens = np.random.default_rng(seed).integers(1, nb * page + 1, b)
    _paged_case(b=b, h=hk * g, hk=hk, nb=nb, page=page, d=32, dv=8,
                nsel=nsel, lengths=list(lens), n_pages=b * nb + 3, seed=seed)


def test_decode_block_skip_matches_no_skip():
    """V-block skipping (per-block max < min threshold) is exact: skipped
    blocks contain no kept entries by construction."""
    from repro.kernels import binary_decode_attention as D
    from repro.core import hamming
    rng = np.random.default_rng(5)
    b, g, t, d, dv, nsel = 2, 3, 128, 64, 16, 6
    q = _bits((b, g, d), 51)
    kb = ops.to_bitplanes(_bits((b, t, d), 52))
    v = jnp.asarray(rng.normal(size=(b, t, dv)).astype(np.float32))
    args = dict(d=d, nsel=jnp.asarray([nsel], jnp.int32),
                scale=jnp.asarray([d ** -0.5], jnp.float32),
                lengths=jnp.full((b,), t, jnp.int32), block_t=16,
                interpret=True)
    out_skip = D.decode_attention(q, kb, v, block_skip=True, **args)
    out_full = D.decode_attention(q, kb, v, block_skip=False, **args)
    np.testing.assert_allclose(np.asarray(out_skip), np.asarray(out_full),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# two-phase page-sparse decode (binary_page_score + compacted-table kernel)
# ---------------------------------------------------------------------------

def _make_pool(b, h, hk, nb, page, d, dv, n_pages, seed=0,
               vdtype=jnp.float32):
    """Contiguous K/V scattered into a shuffled page pool, returned with
    the contiguous originals for oracle calls."""
    t = nb * page
    rng = np.random.default_rng(seed + 2)
    qb = _bits((b, h, d), seed)
    kb = _bits((b, hk, t, d), seed + 1)
    v = jnp.asarray(rng.normal(size=(b, hk, t, dv)).astype(np.float32),
                    dtype=vdtype)
    w = kb.shape[-1]
    perm = rng.permutation(n_pages)[: b * nb]
    bt = perm.reshape(b, nb).astype(np.int32)
    k_pool = np.zeros((n_pages, hk, w, page), np.uint32)
    v_pool = np.zeros((n_pages, hk, page, dv),
                      np.asarray(jnp.zeros((), vdtype)).dtype)
    for bi in range(b):
        for j in range(nb):
            pg = bt[bi, j]
            k_pool[pg] = np.swapaxes(
                np.asarray(kb)[bi, :, j * page:(j + 1) * page], -1, -2)
            v_pool[pg] = np.asarray(v)[bi, :, j * page:(j + 1) * page]
    return (qb, kb, v, jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(bt))


@pytest.mark.parametrize("d", [32, 64, 112])
@pytest.mark.parametrize("hk", [1, 2])
def test_page_score_kernel_matches_ref(d, hk):
    b, g, nb, page = 2, 2, 5, 8
    h = hk * g
    qb, kb, _, k_pool, _, bt = _make_pool(b, h, hk, nb, page, d, 8,
                                          n_pages=b * nb + 2, seed=d)
    lengths = jnp.asarray([nb * page, 3 * page - 5], jnp.int32)
    tables, counts = ops._slot_tables(bt, lengths, page)
    qf = qb.reshape(b, hk, g, -1).reshape(b * hk, g, -1)
    from repro.kernels import binary_page_score as PS
    got = PS.paged_page_scores(qf, k_pool, tables, counts, d=d,
                               n_kv_heads=hk, interpret=True)
    want = ref.page_scores_ref(qb.reshape(b, hk, g, -1), k_pool, bt,
                               d=d, lengths=lengths)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).reshape(b * hk, nb))
    # pure-jnp twin on the gathered bit-plane layout agrees too
    k_bp = ops.to_bitplanes(kb)
    bounds = PS.page_score_bounds(qb.reshape(b, hk, g, -1), k_bp, lengths,
                                  d=d, page=page)
    np.testing.assert_array_equal(np.asarray(bounds),
                                  np.asarray(want))


@given(st.integers(1, 2), st.integers(1, 2), st.integers(1, 3),
       st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_page_score_is_upper_bound(b, hk, g, seed):
    """The phase-1 score must dominate every valid key's exact score in
    its page — otherwise selection could drop a page holding a top-N key
    that dense attention would keep."""
    d, nb, page = 32, 4, 8
    h = hk * g
    qb, kb, _, k_pool, _, bt = _make_pool(b, h, hk, nb, page, d, 4,
                                          n_pages=b * nb + 1, seed=seed)
    lens = np.random.default_rng(seed).integers(1, nb * page + 1, b)
    lengths = jnp.asarray(lens, jnp.int32)
    want = np.asarray(ref.page_scores_ref(qb.reshape(b, hk, g, -1), k_pool,
                                          bt, d=d, lengths=lengths))
    exact = np.asarray(ref.hamming_score_ref(
        qb.reshape(b, hk, g, -1), kb, d))       # [B, Hk, G, T]
    for bi in range(b):
        for kh in range(hk):
            for j in range(nb):
                lo, hi = j * page, min((j + 1) * page, int(lens[bi]))
                if lo >= int(lens[bi]):
                    continue
                page_max = exact[bi, kh, :, lo:hi].max()
                assert want[bi, kh, j] >= page_max


@pytest.mark.parametrize("page_topn", [6, 8, 11])   # == nb, > nb
def test_paged_sparse_full_selection_bit_identical(page_topn):
    """page_topn >= max_blocks: selection keeps everything -> the sparse
    path must be BIT-identical to the dense paged walk."""
    b, h, hk, nb, page, d, dv = 2, 4, 2, 6, 8, 64, 16
    qb, _, _, k_pool, v_pool, bt = _make_pool(b, h, hk, nb, page, d, dv,
                                              n_pages=b * nb + 3, seed=3)
    lengths = jnp.asarray([nb * page, 30], jnp.int32)
    kw = dict(d=d, nsel=10, scale=d ** -0.5, lengths=lengths,
              interpret=True)
    dense = ops.paged_decode_attention(qb, k_pool, v_pool, bt, **kw)
    sparse = ops.paged_decode_attention(qb, k_pool, v_pool, bt,
                                        page_topn=page_topn, **kw)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sparse))


def test_paged_sparse_resident_coverage_bit_identical():
    """resident pages <= page_topn < max_blocks: the compacted table holds
    every RESIDENT page, and block-skip makes zero-count fill blocks
    no-ops in both walks -> still bit-identical to dense."""
    b, h, hk, nb, page, d, dv = 3, 2, 1, 6, 8, 32, 8
    qb, _, _, k_pool, v_pool, bt = _make_pool(b, h, hk, nb, page, d, dv,
                                              n_pages=b * nb + 2, seed=5)
    # at most 3 resident pages per row; page_topn in [3, nb)
    lengths = jnp.asarray([3 * page, 2 * page - 3, 1], jnp.int32)
    kw = dict(d=d, nsel=6, scale=d ** -0.5, lengths=lengths, interpret=True)
    dense = ops.paged_decode_attention(qb, k_pool, v_pool, bt, **kw)
    for ptn in (3, 4, 5):
        sparse = ops.paged_decode_attention(qb, k_pool, v_pool, bt,
                                            page_topn=ptn, **kw)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(sparse))


@pytest.mark.parametrize("page_topn", [1, 2, 3])
def test_paged_sparse_aggressive_matches_ref(page_topn):
    """Aggressive N < resident pages: the compacted-table kernel must
    agree with the mask-formulated sparse oracle (same kept set)."""
    b, h, hk, nb, page, d, dv = 2, 4, 2, 6, 8, 64, 16
    qb, _, _, k_pool, v_pool, bt = _make_pool(b, h, hk, nb, page, d, dv,
                                              n_pages=b * nb + 1, seed=17)
    lengths = jnp.asarray([nb * page, 5 * page - 2], jnp.int32)
    got = ops.paged_decode_attention(qb, k_pool, v_pool, bt, d=d, nsel=10,
                                     scale=d ** -0.5, lengths=lengths,
                                     page_topn=page_topn, interpret=True)
    want = ref.paged_sparse_decode_attention_ref(
        qb.reshape(b, hk, h // hk, -1), k_pool, v_pool, bt, d=d, nsel=10,
        scale=d ** -0.5, lengths=lengths,
        page_topn=page_topn).reshape(b, h, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-6)


@given(st.integers(1, 4), st.integers(0, 500))
@settings(max_examples=15, deadline=None)
def test_select_pages_invariants(n_sel, seed):
    """Selection must always include the frontier page, never emit an
    out-of-range physical id, and keep logical order ascending."""
    r, nb, page, n_pages = 4, 6, 8, 40
    rng = np.random.default_rng(seed)
    scores = jnp.asarray(rng.integers(-64, 65, size=(r, nb)), jnp.int32)
    bt = jnp.asarray(rng.integers(0, n_pages, size=(r, nb)), jnp.int32)
    bt = bt.at[:, -2:].set(-1)                  # unallocated tail sentinels
    lengths = jnp.asarray(rng.integers(1, (nb - 2) * page + 1, size=r),
                          jnp.int32)
    tables, counts, logical = ops.select_pages(scores, bt, lengths,
                                               page=page, n_sel=n_sel)
    tables, counts, logical = (np.asarray(tables), np.asarray(counts),
                               np.asarray(logical))
    frontier = (np.maximum(np.asarray(lengths) - 1, 0)) // page
    for i in range(r):
        assert frontier[i] in logical[i], "frontier page dropped"
        assert (tables[i] >= 0).all(), "drop sentinel leaked into table"
        assert (tables[i] < n_pages).all()
        assert (np.diff(logical[i]) >= 0).all(), "logical order not kept"
        # count bookkeeping matches the logical block positions
        want_cnt = np.clip(int(lengths[i]) - logical[i] * page, 0, page)
        np.testing.assert_array_equal(counts[i], want_cnt)
