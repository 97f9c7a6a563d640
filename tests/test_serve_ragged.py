"""Ragged continuous-batching serving tests.

The load-bearing property: a slot's outputs depend only on its own request
— never on batch composition, other slots' positions, admissions, or
re-fills. Every test cross-checks the ragged scheduler against sequential
one-request-at-a-time serving (binary and full-precision paths).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig
from repro.models import common
from repro.models import model as M
from repro.models.config import HADConfig
from repro.serve import Engine, Request, SamplingParams, ServeConfig

CFG = ModelConfig(name="rag", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                  head_dim=16, param_dtype="float32", q_block=16, remat=False)
KCFG = dataclasses.replace(
    CFG, had=HADConfig(use_kernels=True, kernel_block_q=8, kernel_block_t=16))


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.PRNGKey(10), CFG)


def _scfg(slots, binary, max_len=48, chunk=8, **kw):
    return ServeConfig(max_len=max_len, batch_slots=slots, binary=binary,
                       topn=6, prefill_chunk=chunk, **kw)


def _sequential(cfg, params, prompts, steps, binary, steps_list=None):
    outs = []
    for i, p in enumerate(prompts):
        eng = Engine(cfg, params, _scfg(1, binary))
        rid = eng.submit(p, max_new_tokens=steps_list[i]
                         if steps_list is not None else steps)
        outs.append(eng.run()[rid])
    return outs


# ---------------------------------------------------------------------------
# ragged batches == sequential reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("binary", [True, False])
def test_mixed_lengths_match_sequential(params, binary):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    eng = Engine(CFG, params, _scfg(3, binary))
    ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = eng.run()
    want = _sequential(CFG, params, prompts, 5, binary)
    for rid, w in zip(ids, want):
        np.testing.assert_array_equal(got[rid], w)


def test_mixed_lengths_match_sequential_kernel_path():
    params = M.init_params(jax.random.PRNGKey(10), KCFG)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, n) for n in (12, 7)]
    eng = Engine(KCFG, params, _scfg(2, True))
    ids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    got = eng.run()
    want = _sequential(KCFG, params, prompts, 4, True)
    for rid, w in zip(ids, want):
        np.testing.assert_array_equal(got[rid], w)


@pytest.mark.parametrize("pipelined", [False, True])
def test_logits_sink_sees_every_sampled_token(params, pipelined):
    """The runner's logits sink gets one row per sampled token, prefill
    completion and decode alike, in order; greedy tokens are its argmax."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 64, n) for n in (11, 4, 7)]
    eng = Engine(CFG, params, _scfg(2, True))
    rows: dict[int, list[np.ndarray]] = {}
    eng.runner.logits_sink = (
        lambda rid, row: rows.setdefault(rid, []).append(np.array(row)))
    ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = eng.run_pipelined() if pipelined else eng.run()
    for rid in ids:
        assert len(rows[rid]) == 5
        np.testing.assert_array_equal(
            np.argmax(np.stack(rows[rid]), -1), got[rid])


HCFG = dataclasses.replace(CFG, name="hyb", family="hybrid",
                           layer_pattern="AM", ssm_state=16,
                           ssm_head_dim=16, ssm_chunk=8)


def test_hybrid_ssm_ragged_matches_sequential():
    """Per-slot SSM decode state (h + conv) survives ragged batching,
    masked steps, and slot re-fill in a hybrid attention+Mamba stack."""
    params = M.init_params(jax.random.PRNGKey(13), HCFG)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 64, n) for n in (10, 6, 8)]
    eng = Engine(HCFG, params, _scfg(2, True))
    ids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    got = eng.run()
    want = _sequential(HCFG, params, prompts, 4, True)
    for rid, w in zip(ids, want):
        np.testing.assert_array_equal(got[rid], w)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_ssm_state_does_not_leak_across_slot_refill(seed):
    """A re-filled slot must not see the previous occupant's SSM h/conv
    state (KV caches are length-masked; SSM state is not). Long request
    then short re-fill maximizes undecayed contamination — these seeds
    flipped tokens before in-place admission zeroed fresh rows' state."""
    params = M.init_params(jax.random.PRNGKey(13), HCFG)
    rng = np.random.default_rng(seed)
    p_long, p_short = rng.integers(0, 64, 30), rng.integers(0, 64, 4)
    eng = Engine(HCFG, params, _scfg(1, True))
    eng.submit(p_long, max_new_tokens=4)
    eng.run()
    rid = eng.submit(p_short, max_new_tokens=6)     # re-fill the slot
    got = eng.run()[rid]
    want = _sequential(HCFG, params, [p_short], 6, True)[0]
    np.testing.assert_array_equal(got, want)


def test_cross_cache_does_not_leak_across_slot_refill():
    """A re-filled slot whose new request carries no image must attend a
    ZERO cross cache, not the previous occupant's image K/V."""
    cfg = dataclasses.replace(CFG, name="vlm2", n_layers=2,
                              layer_pattern="AC", n_image_tokens=4,
                              frontend_dim=8)
    params = M.init_params(jax.random.PRNGKey(14), cfg)
    rng = np.random.default_rng(15)
    p_a, p_b = rng.integers(0, 64, 9), rng.integers(0, 64, 5)
    img = rng.normal(size=(1, 4, 8)).astype(np.float32)
    scfg = ServeConfig(max_len=24, batch_slots=1, binary=True, topn=6,
                       prefill_chunk=8)
    eng = Engine(cfg, params, scfg)
    eng.submit(p_a, max_new_tokens=3, extra={"image_embeds": img})
    eng.run()
    rid = eng.submit(p_b, max_new_tokens=3)         # no image this time
    got = eng.run()[rid]
    fresh = Engine(cfg, params, scfg)
    sid = fresh.submit(p_b, max_new_tokens=3)
    np.testing.assert_array_equal(got, fresh.run()[sid])


@pytest.mark.parametrize("binary", [True, False])
def test_slot_refill_and_late_arrivals(params, binary):
    """More requests than slots + a mid-stream arrival: freed slots re-fill
    without restarting residents, and every request still matches its
    sequential reference."""
    rng = np.random.default_rng(2)
    lens = (11, 4, 7, 9, 6)
    steps = (3, 7, 4, 5, 4)   # different lifetimes -> staggered frees
    prompts = [rng.integers(0, 64, n) for n in lens]
    eng = Engine(CFG, params, _scfg(2, binary))
    ids = [eng.submit(p, max_new_tokens=s)
           for p, s in zip(prompts[:4], steps[:4])]
    got = {}
    for _ in range(2):        # residents decode a bit...
        for fr in eng.step():
            got[fr.request_id] = fr.tokens
    ids.append(eng.submit(prompts[4], max_new_tokens=steps[4]))  # ...late
    got.update(eng.run())
    for p, s, rid in zip(prompts, steps, ids):
        e1 = Engine(CFG, params, _scfg(1, binary))
        sid = e1.submit(p, max_new_tokens=s)
        want = e1.run()[sid]
        np.testing.assert_array_equal(got[rid], want)


def test_refill_does_not_disturb_resident_tokens(params):
    """A resident slot's token trajectory is identical whether or not a new
    request was admitted into the other slot mid-stream."""
    rng = np.random.default_rng(3)
    pa, pb = rng.integers(0, 64, 10), rng.integers(0, 64, 6)

    def tokens_a(with_b):
        eng = Engine(CFG, params, _scfg(2, True))
        rid = eng.submit(pa, max_new_tokens=8)
        out = {}
        steps = 0
        while rid not in out:
            if with_b and steps == 2:
                eng.submit(pb, max_new_tokens=2)
            for fr in eng.step():
                out[fr.request_id] = fr.tokens
            steps += 1
        return out[rid]

    np.testing.assert_array_equal(tokens_a(False), tokens_a(True))


# ---------------------------------------------------------------------------
# interleaved chunked prefill
# ---------------------------------------------------------------------------

def _interleave_case(cfg, params, binary, **scfg_kw):
    """Resident slot A decodes while long prompt B is chunk-prefilled;
    A must emit tokens BETWEEN B's prefill chunks, and both must match
    sequential single-request serving exactly."""
    rng = np.random.default_rng(20)
    pa = rng.integers(0, 64, 6)
    pb = rng.integers(0, 64, 33)                  # 5 chunks at chunk=8
    eng = Engine(cfg, params, _scfg(2, binary, **scfg_kw))
    rid_a = eng.submit(pa, max_new_tokens=12)
    while not eng.slots[0].decoding:              # finish A's admission
        eng.step()
    rid_b = eng.submit(pb, max_new_tokens=4)
    interleaved = 0
    got = {}
    while rid_b not in got or rid_a not in got:
        a_before = len(eng.slots[0].generated) if eng.slots[0].request else -1
        for fr in eng.step():
            got[fr.request_id] = fr.tokens
        slot_b = eng.slots[1]
        a_after = len(eng.slots[0].generated) if eng.slots[0].request else -1
        if slot_b.request is not None and slot_b.prefilling \
                and a_after == a_before + 1:
            interleaved += 1                      # A decoded mid-admission
    assert interleaved >= 2, "no decode tokens between B's prefill chunks"
    want = _sequential(cfg, params, [pa, pb], None, binary,
                       steps_list=[12, 4])
    np.testing.assert_array_equal(got[rid_a], want[0])
    np.testing.assert_array_equal(got[rid_b], want[1])


@pytest.mark.parametrize("binary", [True, False])
def test_decode_interleaves_with_prefill_chunks(params, binary):
    _interleave_case(CFG, params, binary)


def test_decode_interleaves_with_prefill_chunks_kernel_path():
    kparams = M.init_params(jax.random.PRNGKey(10), KCFG)
    _interleave_case(KCFG, kparams, True)


def test_admission_is_metadata_only_no_cache_copy(params):
    """Admission must not touch or rebuild the shared cache (the old
    engine's per-admission `at[:, i:i+1].set` tree copy is gone): the
    caches pytree is object-identical until the next step()."""
    eng = Engine(CFG, params, _scfg(2, True))
    leaves_before = jax.tree.leaves(eng.caches)
    eng.submit(np.arange(9, dtype=np.int32), max_new_tokens=2)
    eng._admit(0, eng.queue.popleft())
    leaves_after = jax.tree.leaves(eng.caches)
    assert all(a is b for a, b in zip(leaves_before, leaves_after))


def test_prefill_chunk_lengths_share_one_trace(params):
    """Every prompt length must reuse ONE padded prefill-chunk trace and
    ONE decode trace — no per-remainder-length recompilation."""
    eng = Engine(CFG, params, _scfg(1, True, chunk=8))
    rng = np.random.default_rng(21)
    for n in (5, 8, 13, 21, 3):                   # tails 5, 0, 5, 5, 3
        eng.submit(rng.integers(0, 64, n), max_new_tokens=3)
    eng.run()
    assert eng._step._cache_size() == 2, eng._step._cache_size()


def test_padded_serving_path_never_hits_block_one(params, monkeypatch):
    """Prime prompt lengths used to reach had_infer_attention raw (q-block
    collapses to 1 — one scan step per query). With pad-to-chunk serving
    every traced chunk is the configured chunk size, so choose_block must
    never degenerate."""
    from repro.core import attention as A
    recorded = []
    real = A.choose_block

    def spy(s, target=512):
        blk = real(s, target)
        recorded.append((s, target, blk))
        return blk

    monkeypatch.setattr(A, "choose_block", spy)
    eng = Engine(CFG, params, _scfg(1, True, chunk=8))
    rng = np.random.default_rng(23)
    for n in (7, 13):                             # prime prompt lengths
        eng.submit(rng.integers(0, 64, n), max_new_tokens=2)
    eng.run()
    assert recorded, "serving no longer exercises choose_block?"
    # s == 1 is the decode step (one query: block 1 is exact, not
    # degenerate); every multi-token chunk must keep a real block size
    multi = [(s, t, blk) for s, t, blk in recorded if s > 1]
    assert multi and min(blk for _, _, blk in multi) > 1, recorded


def test_finish_at_max_len_resets_slot_and_refills(params):
    """A request that fills its slot exactly to max_len must leave the
    freed slot with length 0 (stale lengths false-tripped the lockstep
    decode() guard and fed garbage positions), and the slot must re-fill
    cleanly."""
    rng = np.random.default_rng(22)
    pa = rng.integers(0, 64, 12)                  # 12 + 4 == max_len
    eng = Engine(CFG, params, _scfg(2, True, max_len=16))
    rid = eng.submit(pa, max_new_tokens=4)
    first = eng.run()[rid]
    assert first.shape == (4,)
    np.testing.assert_array_equal(eng.lengths, [0, 0])
    pb = rng.integers(0, 64, 5)                   # re-fill the freed slot
    rid2 = eng.submit(pb, max_new_tokens=3)
    got = eng.run()[rid2]
    e1 = Engine(CFG, params, _scfg(1, True, max_len=16))
    sid = e1.submit(pb, max_new_tokens=3)
    np.testing.assert_array_equal(got, e1.run()[sid])


# ---------------------------------------------------------------------------
# paged KV cache (block tables) vs contiguous cache
# ---------------------------------------------------------------------------

PAGED = dict(paged=True, page_size=8)


@pytest.mark.parametrize("binary", [True, False])
def test_paged_matches_contiguous(params, binary):
    """Paged serving (block-table addressed page pool) must be pinned to
    the dense-cache scheduler token-for-token — binary and fp paths."""
    rng = np.random.default_rng(30)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    dense = Engine(CFG, params, _scfg(3, binary))
    ids_d = [dense.submit(p, max_new_tokens=5) for p in prompts]
    want = dense.run()
    paged = Engine(CFG, params, _scfg(3, binary, **PAGED))
    ids_p = [paged.submit(p, max_new_tokens=5) for p in prompts]
    got = paged.run()
    for a, b in zip(ids_d, ids_p):
        np.testing.assert_array_equal(got[b], want[a])
    assert paged.stats["preemptions"] == 0      # dense-equivalent pool


def test_paged_matches_contiguous_kernel_path():
    """Paged Pallas decode kernel (block-table prefetch) + gathered-page
    prefill kernel vs the contiguous kernels."""
    kparams = M.init_params(jax.random.PRNGKey(10), KCFG)
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, 64, n) for n in (12, 7)]
    dense = Engine(KCFG, kparams, _scfg(2, True))
    ids_d = [dense.submit(p, max_new_tokens=4) for p in prompts]
    want = dense.run()
    paged = Engine(KCFG, kparams, _scfg(2, True, **PAGED))
    ids_p = [paged.submit(p, max_new_tokens=4) for p in prompts]
    got = paged.run()
    for a, b in zip(ids_d, ids_p):
        np.testing.assert_array_equal(got[b], want[a])


def test_paged_hybrid_ssm_matches_sequential():
    """Paged attention pools compose with dense SSM decode state: the
    active-select must keep applying to SSM/conv leaves while the shared
    pools (no batch axis) are masked at scatter time."""
    params = M.init_params(jax.random.PRNGKey(13), HCFG)
    rng = np.random.default_rng(32)
    prompts = [rng.integers(0, 64, n) for n in (10, 6, 8)]
    eng = Engine(HCFG, params, _scfg(2, True, **PAGED))
    ids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    got = eng.run()
    want = _sequential(HCFG, params, prompts, 4, True)
    for rid, w in zip(ids, want):
        np.testing.assert_array_equal(got[rid], w)


@pytest.mark.parametrize("binary", [True, False])
def test_paged_interleaved_decode_between_chunks(params, binary):
    """The chunked-prefill/decode interleaving contract holds unchanged
    over paged caches (pages allocated lazily per chunk / per token)."""
    _interleave_case(CFG, params, binary, **PAGED)


def test_paged_interleave_kernel_path():
    kparams = M.init_params(jax.random.PRNGKey(10), KCFG)
    _interleave_case(KCFG, kparams, True, **PAGED)


@pytest.mark.parametrize("binary", [True, False])
def test_paged_preemption_roundtrip(params, binary):
    """Pool exhaustion preempts the youngest resident (pages freed,
    request re-queued) and the re-admitted request still produces its
    sequential-reference tokens — a full preemption -> re-prefill -> keep
    decoding round trip, binary and fp."""
    rng = np.random.default_rng(33)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    eng = Engine(CFG, params, _scfg(3, binary, paged=True, page_size=8,
                                    n_pages=3))
    ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = eng.run()
    assert eng.stats["preemptions"] > 0, "pool never exhausted: test is void"
    want = _sequential(CFG, params, prompts, 5, binary)
    for rid, w in zip(ids, want):
        np.testing.assert_array_equal(got[rid], w)
    assert eng.allocator.in_use == 0            # all pages returned


def test_paged_preemption_roundtrip_kernel_path():
    kparams = M.init_params(jax.random.PRNGKey(10), KCFG)
    rng = np.random.default_rng(34)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    eng = Engine(KCFG, kparams, _scfg(3, True, paged=True, page_size=8,
                                      n_pages=3))
    ids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    got = eng.run()
    assert eng.stats["preemptions"] > 0
    want = _sequential(KCFG, kparams, prompts, 4, True)
    for rid, w in zip(ids, want):
        np.testing.assert_array_equal(got[rid], w)


def test_paged_double_preemption_does_not_duplicate_tokens(params):
    """A request preempted TWICE must not re-fold already-replayed
    generated tokens into its prompt (the original prompt length lives on
    the slot — a _resume lookup in _preempt always missed, because
    _admit pops entries, so the second eviction duplicated the replay
    and corrupted the continuation). Tight pool + long generations force
    repeated evictions of the same requests."""
    rng = np.random.default_rng(40)
    prompts = [rng.integers(0, 64, n) for n in (13, 9, 11)]
    eng = Engine(CFG, params, _scfg(3, True, paged=True, page_size=8,
                                    n_pages=4))
    ids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    got = eng.run()
    assert eng.stats["preemptions"] >= 2, eng.stats
    want = _sequential(CFG, params, prompts, 12, True)
    for rid, w in zip(ids, want):
        np.testing.assert_array_equal(got[rid], w)


def test_paged_victim_skips_unreplayable_seq_extras(params):
    """Recompute-style resume cannot replay sequence-aligned extras
    (e.g. frames) for generated positions: such slots must never be
    picked as preemption victims, and if no clean victim exists the
    engine raises instead of silently corrupting."""
    from repro.serve.engine import Request
    eng = Engine(CFG, params, _scfg(2, True, paged=True, page_size=8,
                                    n_pages=4))
    r0 = Request(tokens=np.arange(6, dtype=np.int32), request_id=0,
                 extra={"frames": np.zeros((1, 6, 4), np.float32)})
    r1 = Request(tokens=np.arange(4, dtype=np.int32), request_id=1)
    eng._admit(0, r0)
    eng._admit(1, r1)
    eng.slots[0].generated = [3]        # frames slot has emitted a token
    eng.slots[1].generated = [5]
    assert eng._pick_victim() == 1      # younger AND clean -> slot 1
    eng.slots[1].request = None         # only the frames slot remains
    with pytest.raises(RuntimeError):
        eng._pick_victim()
    eng.slots[0].generated = []         # no tokens yet -> clean replay
    assert eng._pick_victim() == 0


def test_paged_prefill_chunk_lengths_share_one_trace(params):
    """Paged serving keeps the compile-count pin: ONE padded prefill-chunk
    trace + ONE decode trace — block tables are traced arguments, so
    neither prompt length nor page placement recompiles."""
    eng = Engine(CFG, params, _scfg(1, True, **PAGED))
    rng = np.random.default_rng(35)
    for n in (5, 8, 13, 21, 3):
        eng.submit(rng.integers(0, 64, n), max_new_tokens=3)
    eng.run()
    assert eng._step._cache_size() == 2, eng._step._cache_size()


def test_paged_submit_rejects_request_larger_than_pool(params):
    eng = Engine(CFG, params, _scfg(1, True, paged=True, page_size=8,
                                    n_pages=2))
    with pytest.raises(ValueError):
        eng.submit(np.zeros(15, np.int32), max_new_tokens=3)  # 18 tok > 16


def test_paged_lockstep_prefill_decode(params):
    """The hand-driven lockstep API works over paged caches (pages
    allocated up front per uniform prefill, strict no-preempt mode)."""
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(12), (2, 8), 0, 64))
    dense = Engine(CFG, params, _scfg(2, True, max_len=16))
    paged = Engine(CFG, params, _scfg(2, True, max_len=16, **PAGED))
    ld = dense.prefill(prompts)
    lp = paged.prefill(prompts)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                               rtol=1e-5, atol=1e-5)
    tok = np.asarray(jnp.argmax(lp, -1))
    np.testing.assert_allclose(np.asarray(paged.decode(tok)),
                               np.asarray(dense.decode(tok)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(paged.lengths, [9, 9])


# ---------------------------------------------------------------------------
# page-aligned swap-out preemption
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("binary", [True, False])
def test_swap_preemption_bit_identical_with_zero_reprefill(params, binary):
    """Acceptance pin: an overcommitted pool with swap space serves every
    request bit-identically to the unpreempted dense baseline, swapped
    victims re-prefill ZERO tokens, and both pools drain clean."""
    rng = np.random.default_rng(33)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    dense = Engine(CFG, params, _scfg(3, binary))
    ids_d = [dense.submit(p, max_new_tokens=5) for p in prompts]
    want = dense.run()
    eng = Engine(CFG, params, _scfg(3, binary, paged=True, page_size=8,
                                    n_pages=3, swap_pages=8))
    ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = eng.run()
    assert eng.stats["swap_outs"] > 0, "pool never forced a swap: test void"
    assert eng.stats["swap_ins"] == eng.stats["swap_outs"]
    assert eng.stats["replayed_tokens"] == 0     # zero re-prefill
    assert eng.stats["swapped_tokens"] > 0
    for a, b in zip(ids_d, ids):
        np.testing.assert_array_equal(got[b], want[a])
    assert eng.allocator.in_use == 0             # all device pages returned
    assert eng.swap.in_use == 0                  # all swap space released


def test_swap_preemption_roundtrip_kernel_path():
    kparams = M.init_params(jax.random.PRNGKey(10), KCFG)
    rng = np.random.default_rng(34)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    eng = Engine(KCFG, kparams, _scfg(3, True, paged=True, page_size=8,
                                      n_pages=3, swap_pages=8))
    ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = eng.run()
    assert eng.stats["swap_outs"] > 0
    assert eng.stats["replayed_tokens"] == 0
    want = _sequential(KCFG, kparams, prompts, 5, True)
    for rid, w in zip(ids, want):
        np.testing.assert_array_equal(got[rid], w)


def test_swap_matches_recompute_preemption_outputs(params):
    """Swap-out is a pure mechanism change: the same overcommitted
    workload yields identical tokens with swap on (zero re-prefill) and
    off (recompute replay) — while doing strictly less prefill work."""
    rng = np.random.default_rng(35)
    prompts = [rng.integers(0, 64, n) for n in (13, 9, 11)]
    outs, ptoks = {}, {}
    for swap in (0, 8):
        eng = Engine(CFG, params, _scfg(3, True, paged=True, page_size=8,
                                        n_pages=4, swap_pages=swap))
        ids = [eng.submit(p, max_new_tokens=12) for p in prompts]
        got = eng.run()
        assert eng.stats["preemptions"] >= 2, eng.stats
        if swap:
            assert eng.stats["swap_outs"] > 0
        else:
            assert eng.stats["replayed_tokens"] > 0
        outs[swap] = [got[r] for r in ids]
        ptoks[swap] = eng.stats["prefill_tokens"]
    for a, b in zip(outs[0], outs[8]):
        np.testing.assert_array_equal(a, b)
    assert ptoks[8] < ptoks[0]                   # swapped work not redone


def test_swap_composes_with_prefix_cache(params):
    """Swap x prefix-cache interplay: shared prefixes + pool pressure +
    swap-outs still serve cold-identical tokens, and swapped-in pages
    never alias the index (every indexed page is allocator-cached; the
    restored private copies are not)."""
    rng = np.random.default_rng(36)
    shared = rng.integers(0, 64, 2 * 8)
    prompts = [np.concatenate([shared, rng.integers(0, 64, 5 + i)])
               for i in range(3)]
    eng = Engine(CFG, params, _scfg(3, True, paged=True, page_size=8,
                                    n_pages=4, prefix_cache=True,
                                    swap_pages=8))
    ids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    got = eng.run()
    assert eng.stats["preemptions"] > 0, "pool never pressured: test void"
    for rid, p in zip(ids, prompts):
        e1 = Engine(CFG, params, _scfg(1, True))
        sid = e1.submit(p, max_new_tokens=8)
        np.testing.assert_array_equal(got[rid], e1.run()[sid])
    # index consistency: every surviving entry maps to a cached page
    for page in eng.prefix._page_of.values():
        assert eng.allocator.is_cached(page)
    assert eng.allocator.in_use == 0 and eng.swap.in_use == 0


def test_swap_keeps_one_prefill_one_decode_trace(params):
    """Swap transfers are eager gathers/scatters outside the jitted step:
    a swap-heavy run keeps exactly one prefill-chunk trace plus one
    decode trace."""
    eng = Engine(CFG, params, _scfg(3, True, paged=True, page_size=8,
                                    n_pages=3, swap_pages=8))
    rng = np.random.default_rng(37)
    for n in (13, 5, 9):
        eng.submit(rng.integers(0, 64, n), max_new_tokens=5)
    eng.run()
    assert eng.stats["swap_outs"] > 0
    assert eng._step._cache_size() == 2, eng._step._cache_size()


def test_swap_rejected_for_dense_cache_only(params):
    """Non-paged caches have no pages to swap — still a construction
    error. Stateful (SSM / cross-attention) models are no longer
    rejected: their per-slot state lives in the pooled state allocation
    and swaps atomically with the KV pages."""
    with pytest.raises(ValueError, match="paged"):
        Engine(CFG, params, _scfg(1, True, swap_pages=4))
    hparams = M.init_params(jax.random.PRNGKey(13), HCFG)
    eng = Engine(HCFG, hparams, _scfg(1, True, paged=True, page_size=8,
                                      swap_pages=4))
    assert eng.statepool is not None


@pytest.mark.parametrize("binary", [True, False])
def test_hybrid_swap_bit_identical_with_zero_reprefill(binary):
    """Acceptance pin: an overcommitted hybrid (attention+Mamba) engine
    with swap space serves every request bit-identically to the
    unpreempted baseline — the recurrent state entry is gathered to host
    and restored verbatim alongside the KV pages."""
    hparams = M.init_params(jax.random.PRNGKey(13), HCFG)
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    dense = Engine(HCFG, hparams, _scfg(3, binary))
    ids_d = [dense.submit(p, max_new_tokens=5) for p in prompts]
    want = dense.run()
    eng = Engine(HCFG, hparams, _scfg(3, binary, paged=True, page_size=8,
                                      n_pages=3, swap_pages=8))
    ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = eng.run()
    assert eng.stats["swap_outs"] > 0, "pool never forced a swap: test void"
    assert eng.stats["replayed_tokens"] == 0     # zero re-prefill
    for a, b in zip(ids_d, ids):
        np.testing.assert_array_equal(got[b], want[a])
    assert eng.allocator.in_use == 0
    assert eng.swap.in_use == 0
    assert eng.statepool.n_held == 0             # all state entries returned
    eng.statepool.check()


def test_hybrid_swap_roundtrip_kernel_path():
    kcfg = dataclasses.replace(
        HCFG, had=HADConfig(use_kernels=True, kernel_block_q=8,
                            kernel_block_t=16))
    kparams = M.init_params(jax.random.PRNGKey(13), kcfg)
    rng = np.random.default_rng(42)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    eng = Engine(kcfg, kparams, _scfg(3, True, paged=True, page_size=8,
                                      n_pages=3, swap_pages=8))
    ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = eng.run()
    assert eng.stats["swap_outs"] > 0
    assert eng.stats["replayed_tokens"] == 0
    want = _sequential(kcfg, kparams, prompts, 5, True)
    for rid, w in zip(ids, want):
        np.testing.assert_array_equal(got[rid], w)


def test_hybrid_recompute_preemption_matches_and_state_is_fresh():
    """Swap off: hybrid preemption falls back to recompute replay. The
    re-prefill re-derives the recurrent state from scratch, so outputs
    still match the unpreempted baseline — pinning that a re-filled slot
    never inherits its previous occupant's h/conv state under chunked
    prefill x preemption."""
    hparams = M.init_params(jax.random.PRNGKey(13), HCFG)
    rng = np.random.default_rng(43)
    prompts = [rng.integers(0, 64, n) for n in (13, 9, 11)]
    dense = Engine(HCFG, hparams, _scfg(3, True))
    ids_d = [dense.submit(p, max_new_tokens=12) for p in prompts]
    want = dense.run()
    eng = Engine(HCFG, hparams, _scfg(3, True, paged=True, page_size=8,
                                      n_pages=4))
    ids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    got = eng.run()
    assert eng.stats["preemptions"] >= 2, eng.stats
    assert eng.stats["replayed_tokens"] > 0
    for a, b in zip(ids_d, ids):
        np.testing.assert_array_equal(got[b], want[a])


def test_cross_state_pooled_swap_and_refill_no_leak():
    """Cross-attention (AC) engine under pool pressure with swap: the
    pooled cross-cache entry swaps atomically with the KV pages, and an
    image-free request re-filling a slot that previously held an image
    request attends a ZERO cross cache, not the old occupant's image
    K/V — under chunked prefill x preemption x re-fill."""
    cfg = dataclasses.replace(CFG, name="vlm3", n_layers=2,
                              layer_pattern="AC", n_image_tokens=4,
                              frontend_dim=8)
    cparams = M.init_params(jax.random.PRNGKey(14), cfg)
    rng = np.random.default_rng(45)
    img = rng.normal(size=(1, 4, 8)).astype(np.float32)
    reqs = [(rng.integers(0, 64, 13), {"image_embeds": img}),
            (rng.integers(0, 64, 5), None),
            (rng.integers(0, 64, 9), {"image_embeds": img})]
    eng = Engine(cfg, cparams, _scfg(2, True, paged=True, page_size=8,
                                     n_pages=3, swap_pages=8))
    ids = [eng.submit(p, max_new_tokens=5, extra=e) for p, e in reqs]
    got = eng.run()
    assert eng.stats["preemptions"] > 0, eng.stats
    for rid, (p, e) in zip(ids, reqs):
        ref = Engine(cfg, cparams, _scfg(1, True))
        sid = ref.submit(p, max_new_tokens=5, extra=e)
        np.testing.assert_array_equal(got[rid], ref.run()[sid])
    assert eng.statepool.n_held == 0
    eng.statepool.check()


def test_hybrid_swap_keeps_one_prefill_one_decode_trace():
    """The pooled-state step stays on the shared traces: a swap-heavy
    hybrid run keeps exactly one prefill-chunk trace plus one decode
    trace (state gathers/scatters are eager, outside the jit)."""
    hparams = M.init_params(jax.random.PRNGKey(13), HCFG)
    eng = Engine(HCFG, hparams, _scfg(3, True, paged=True, page_size=8,
                                      n_pages=3, swap_pages=8,
                                      prefix_cache=True))
    rng = np.random.default_rng(44)
    for n in (13, 5, 9):
        eng.submit(rng.integers(0, 64, n), max_new_tokens=5)
    eng.run()
    assert eng.stats["swap_outs"] > 0
    assert eng._step._cache_size() == 2, eng._step._cache_size()


# ---------------------------------------------------------------------------
# scheduler policies + idle multi-chunk prefill
# ---------------------------------------------------------------------------

def test_shortest_prompt_policy_admits_short_first(params):
    eng = Engine(CFG, params, _scfg(1, True, policy="shortest-prompt"))
    rng = np.random.default_rng(36)
    rid_long = eng.submit(rng.integers(0, 64, 20), max_new_tokens=6)
    rid_short = eng.submit(rng.integers(0, 64, 4), max_new_tokens=6)
    eng.step()
    assert eng.slots[0].request.request_id == rid_short
    out = eng.run()
    assert sorted(out) == sorted([rid_long, rid_short])
    # fcfs keeps submission order
    eng2 = Engine(CFG, params, _scfg(1, True))
    rid_l2 = eng2.submit(rng.integers(0, 64, 20), max_new_tokens=6)
    eng2.submit(rng.integers(0, 64, 4), max_new_tokens=6)
    eng2.step()
    assert eng2.slots[0].request.request_id == rid_l2


def test_shortest_prompt_outputs_match_fcfs_outputs(params):
    """Admission order is pure host-side scheduling: every request's
    tokens are identical under either policy."""
    rng = np.random.default_rng(37)
    prompts = [rng.integers(0, 64, n) for n in (17, 4, 11, 7)]
    outs = {}
    for policy in ("fcfs", "shortest-prompt"):
        eng = Engine(CFG, params, _scfg(2, True, policy=policy))
        ids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        got = eng.run()
        outs[policy] = [got[r] for r in ids]
    for a, b in zip(outs["fcfs"], outs["shortest-prompt"]):
        np.testing.assert_array_equal(a, b)


def test_shortest_prompt_ranks_preempted_by_original_length(params):
    """A preempted request's tokens grow by the folded-in replay; the
    shortest-prompt rank must use its ORIGINAL prompt length, or every
    eviction would deprioritize it further (starvation under a stream of
    short submissions)."""
    from repro.serve.engine import Request
    eng = Engine(CFG, params, _scfg(1, True, policy="shortest-prompt",
                                    paged=True, page_size=8, n_pages=6))
    # preempted request: originally 5 tokens, grown to 9 by the replay
    rp = Request(tokens=np.arange(9, dtype=np.int32), request_id=0)
    eng._resume[0] = {"prompt_len": 5, "generated": [1, 2, 3, 4],
                      "rng": np.random.default_rng(0)}
    fresh = Request(tokens=np.arange(7, dtype=np.int32), request_id=1)
    eng.queue.extend([fresh, rp])
    assert eng._pop_next() is rp        # 5 < 7 despite 9 carried tokens
    assert eng._pop_next() is fresh


def test_idle_batch_prefills_whole_prompt_in_one_step(params):
    """With no decoding resident the per-step budget lifts: a 33-token
    prompt (5 chunks at chunk=8) admits fully within one step()."""
    eng = Engine(CFG, params, _scfg(2, True))
    rng = np.random.default_rng(38)
    eng.submit(rng.integers(0, 64, 33), max_new_tokens=3)
    eng.step()
    assert eng.stats["prefill_chunks"] == 5
    assert eng.slots[0].decoding


def test_busy_batch_still_spends_one_chunk_per_step(params):
    """A decoding resident caps the budget at one chunk (the ITL bound
    interleaved prefill exists for)."""
    rng = np.random.default_rng(39)
    eng = Engine(CFG, params, _scfg(2, True))
    eng.submit(rng.integers(0, 64, 5), max_new_tokens=8)
    while not eng.slots[0].decoding:
        eng.step()
    chunks0 = eng.stats["prefill_chunks"]
    eng.submit(rng.integers(0, 64, 33), max_new_tokens=2)
    eng.step()
    assert eng.stats["prefill_chunks"] == chunks0 + 1


# ---------------------------------------------------------------------------
# scheduler mechanics
# ---------------------------------------------------------------------------

def test_queue_overflow_and_order(params):
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 64, 5 + i) for i in range(5)]
    eng = Engine(CFG, params, _scfg(2, True))
    ids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    got = eng.run()
    assert sorted(got) == sorted(ids)
    assert all(got[i].shape == (3,) for i in ids)


def test_eos_stops_early(params):
    rng = np.random.default_rng(5)
    p = rng.integers(0, 64, 8)
    eng = Engine(CFG, params, _scfg(1, True))
    rid = eng.submit(p, max_new_tokens=10)
    first = eng.run()[rid]
    eos = int(first[2])
    eng2 = Engine(CFG, params, _scfg(1, True))
    rid2 = eng2.submit(p, max_new_tokens=10, eos_token=eos)
    out = eng2.run()[rid2]
    stop = int(np.argmax(first == eos))      # first occurrence of eos
    np.testing.assert_array_equal(out, first[:stop + 1])
    assert out[-1] == eos


def test_submit_rejects_oversized(params):
    eng = Engine(CFG, params, _scfg(1, True, max_len=16))
    with pytest.raises(ValueError):
        eng.submit(np.zeros(10, np.int32), max_new_tokens=7)


def test_temperature_topk_sampling_seeded(params):
    rng = np.random.default_rng(6)
    p = rng.integers(0, 64, 6)
    sp = SamplingParams(temperature=0.8, top_k=8, seed=123)
    outs = []
    for _ in range(2):
        eng = Engine(CFG, params, _scfg(1, True))
        rid = eng.submit(Request(tokens=p, max_new_tokens=6, sampling=sp))
        outs.append(eng.run()[rid])
    np.testing.assert_array_equal(outs[0], outs[1])  # same seed -> same draw
    eng = Engine(CFG, params, _scfg(1, True))
    rid = eng.submit(p, max_new_tokens=6,
                     sampling=SamplingParams(temperature=0.8, top_k=8,
                                             seed=7))
    other = eng.run()[rid]
    assert not np.array_equal(outs[0], other)  # different seed -> different


def test_lengths_dtype_int32(params):
    eng = Engine(CFG, params, _scfg(2, True))
    assert eng.lengths.dtype == np.int32


def test_topk_sampling_keeps_exactly_k_on_ties():
    """Ties at the k-th logit must not widen the candidate set beyond
    top_k (`l >= kth` kept every tied logit); ties break by lowest index."""
    from repro.serve.engine import _sample_token
    logits = np.array([2.0, 1.0, 1.0, 1.0, 1.0, 0.5], np.float32)
    sp = SamplingParams(temperature=1.0, top_k=2, seed=0)
    rng = np.random.default_rng(0)
    drawn = {_sample_token(logits, sp, rng) for _ in range(200)}
    assert drawn <= {0, 1}, drawn                 # index 1 wins the tie
    assert drawn == {0, 1}                        # both survivors reachable
    # k-th value unique -> unchanged behavior
    sp3 = SamplingParams(temperature=1.0, top_k=3, seed=0)
    logits2 = np.array([3.0, 2.0, 1.0, 0.5], np.float32)
    drawn2 = {_sample_token(logits2, sp3, rng) for _ in range(200)}
    assert drawn2 == {0, 1, 2}


# ---------------------------------------------------------------------------
# serving-state bug sweep regressions
# ---------------------------------------------------------------------------

def test_submit_request_never_aliases_caller_objects(params):
    """submit(Request) must deep-copy `sampling` and `extra` (and arrays
    inside `extra`): dataclasses.replace alone is shallow, so a caller
    mutating after submit rewrote the queued request."""
    rng = np.random.default_rng(70)
    prompt = rng.integers(0, 64, 6)
    sp = SamplingParams(temperature=0.8, top_k=8, seed=123)
    extra = {"frames": np.zeros((1, 6, 4), np.float32)}
    eng = Engine(CFG, params, _scfg(1, True))
    eng.submit(Request(tokens=prompt, max_new_tokens=6,
                       sampling=sp, extra=extra))
    # the convenience overload must copy just the same
    eng.submit(prompt, max_new_tokens=6, sampling=sp, extra=extra)
    for q in eng.queue:
        assert q.sampling is not sp
        assert q.extra is not extra
        assert not np.shares_memory(q.extra["frames"], extra["frames"])

    def run_once(mutate):
        sp_local = dataclasses.replace(sp)
        e = Engine(CFG, params, _scfg(1, True))
        rid = e.submit(Request(tokens=prompt, max_new_tokens=6,
                               sampling=sp_local))
        if mutate:                      # caller reuses its objects
            sp_local.temperature = 0.0
            sp_local.seed = 999
        return e.run()[rid]

    np.testing.assert_array_equal(run_once(False), run_once(True))


def test_lockstep_prefill_raises_on_queued_requests(params):
    """prefill() drops residents by contract, but silently discarding
    QUEUED requests was never the contract — it must raise."""
    eng = Engine(CFG, params, _scfg(1, True))
    eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=4)
    eng.submit(np.arange(7, dtype=np.int32), max_new_tokens=4)  # queued
    eng.step()
    with pytest.raises(RuntimeError, match="queued"):
        eng.prefill(np.zeros((1, 4), np.int32))


def test_lockstep_prefill_clears_dropped_resident_state(params):
    """Dropping residents must clear generated/next_token/rng and stale
    _resume entries — the old prefill() left them, so the next occupant's
    bookkeeping started from another request's state."""
    rng = np.random.default_rng(71)
    eng = Engine(CFG, params, _scfg(2, True, max_len=16))
    eng.submit(rng.integers(0, 64, 5), max_new_tokens=8)
    while not eng.slots[0].decoding:
        eng.step()
    eng.step()
    assert eng.slots[0].generated               # resident mid-generation
    eng._resume[99] = {"prompt_len": 1, "generated": [], "rng": None}
    prompts = np.asarray(rng.integers(0, 64, (2, 8)), np.int32)
    logits = eng.prefill(prompts)
    assert logits.shape == (2, CFG.vocab_size)
    for slot in eng.slots:
        assert slot.request is None and slot.generated == []
        assert slot.next_token == 0 and slot.rng is None
    assert not eng._resume
    # the lockstep session proceeds as if freshly constructed
    fresh = Engine(CFG, params, _scfg(2, True, max_len=16))
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(fresh.prefill(prompts)),
                               rtol=1e-5, atol=1e-5)


def test_reset_stats_keeps_current_residents_watermark(params):
    """reset_stats() mid-flight must restart max_residents at the CURRENT
    resident count (like reset_watermark), not zero — serve_bench resets
    after warm-up while slots are still resident."""
    eng = Engine(CFG, params, _scfg(2, True))
    eng.submit(np.arange(9, dtype=np.int32), max_new_tokens=12)
    eng.step()
    assert eng.stats["max_residents"] == 1
    eng.reset_stats()
    assert eng.stats["max_residents"] == 1      # resident survived reset
    assert eng.stats["decode_steps"] == 0       # counters did reset
    eng.run()
    # idle engine resets to zero as before
    eng.reset_stats()
    assert eng.stats["max_residents"] == 0


# ---------------------------------------------------------------------------
# chunked prefill extra routing (the dropped-`extra` bug)
# ---------------------------------------------------------------------------

def test_prefill_chunks_keep_image_embeds():
    """Prompt longer than prefill_chunk with cross-attention image context:
    chunked prefill must equal single-chunk prefill (the old engine dropped
    `extra` after chunk 0 — here the cross cache must survive chunking)."""
    cfg = dataclasses.replace(
        CFG, name="vlm", n_layers=2, layer_pattern="AC",
        n_image_tokens=4, frontend_dim=8)
    params = M.init_params(jax.random.PRNGKey(11), cfg)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 64, 12)
    img = rng.normal(size=(1, 4, 8)).astype(np.float32)
    outs = {}
    for chunk in (4, 16):  # 3 chunks vs single chunk
        eng = Engine(cfg, params, ServeConfig(max_len=24, batch_slots=1,
                                              binary=True, topn=6,
                                              prefill_chunk=chunk))
        rid = eng.submit(prompt, max_new_tokens=4,
                         extra={"image_embeds": img})
        outs[chunk] = eng.run()[rid]
    np.testing.assert_array_equal(outs[4], outs[16])


# ---------------------------------------------------------------------------
# per-slot RoPE offsets
# ---------------------------------------------------------------------------

def test_apply_rope_per_batch_positions_match_loop():
    x = jnp.asarray(np.random.default_rng(8).normal(size=(3, 2, 4, 8))
                    .astype(np.float32))
    pos = jnp.asarray([[0, 1, 2, 3], [5, 6, 7, 8], [2, 3, 4, 5]])
    batched = common.apply_rope(x, pos)
    for b in range(3):
        one = common.apply_rope(x[b:b + 1], pos[b])
        np.testing.assert_allclose(np.asarray(batched[b]),
                                   np.asarray(one[0]), rtol=1e-6)


# ---------------------------------------------------------------------------
# legacy lockstep API still works (and is now ragged-safe)
# ---------------------------------------------------------------------------

def test_lockstep_prefill_decode(params):
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(12), (2, 8), 0, 64))
    eng = Engine(CFG, params, _scfg(2, True, max_len=16))
    logits = eng.prefill(prompts)
    assert logits.shape == (2, CFG.vocab_size)
    tok = np.asarray(jnp.argmax(logits, -1))
    logits2 = eng.decode(tok)
    assert np.isfinite(np.asarray(logits2)).all()
    np.testing.assert_array_equal(eng.lengths, [9, 9])


# ---------------------------------------------------------------------------
# two-phase top-N page-sparse decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("binary", [True, False])
@pytest.mark.parametrize("page_topn", [3, 6])   # >= resident pages; == nb
def test_page_sparse_full_coverage_bit_identical(params, binary, page_topn):
    """Acceptance pin: page_topn >= resident pages selects every resident
    page in logical order, so sparse decode is BIT-identical to the dense
    paged walk — binary and fp paths. Prompts cap at 18 tokens ->
    <= 3 resident pages of 8, so page_topn=3 already covers (and 6 ==
    max_blocks covers trivially)."""
    rng = np.random.default_rng(50)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    dense = Engine(CFG, params, _scfg(3, binary, **PAGED))
    ids_d = [dense.submit(p, max_new_tokens=5) for p in prompts]
    want = dense.run()
    sparse = Engine(CFG, params, _scfg(3, binary, **PAGED,
                                       page_topn=page_topn))
    ids_s = [sparse.submit(p, max_new_tokens=5) for p in prompts]
    got = sparse.run()
    for a, b in zip(ids_d, ids_s):
        np.testing.assert_array_equal(got[b], want[a])


def test_page_sparse_full_coverage_kernel_path():
    """Same pin through the Pallas kernels: phase-1 page-score kernel +
    compacted-table decode kernel vs the dense paged kernel."""
    kparams = M.init_params(jax.random.PRNGKey(10), KCFG)
    rng = np.random.default_rng(51)
    prompts = [rng.integers(0, 64, n) for n in (12, 7)]
    dense = Engine(KCFG, kparams, _scfg(2, True, **PAGED))
    ids_d = [dense.submit(p, max_new_tokens=4) for p in prompts]
    want = dense.run()
    sparse = Engine(KCFG, kparams, _scfg(2, True, **PAGED, page_topn=3))
    ids_s = [sparse.submit(p, max_new_tokens=4) for p in prompts]
    got = sparse.run()
    for a, b in zip(ids_d, ids_s):
        np.testing.assert_array_equal(got[b], want[a])


def test_page_sparse_composes_with_prefix_cache(params):
    """Warm prefix-cache residents (pages mapped from the index, not
    prefilled) must score and select identically: the warm sparse pass
    stays pinned to the cold dense baseline."""
    rng = np.random.default_rng(52)
    shared = rng.integers(0, 64, 2 * 8)
    prompts = [np.concatenate([shared, rng.integers(0, 64, 4 + i)])
               for i in range(3)]
    dense = Engine(CFG, params, _scfg(3, True, **PAGED))
    ids_d = [dense.submit(p, max_new_tokens=5) for p in prompts]
    want = dense.run()
    eng = Engine(CFG, params, _scfg(3, True, **PAGED, prefix_cache=True,
                                    page_topn=4))
    # cold wave populates the index; repeat wave serves prefix-warm
    ids_cold = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got_cold = eng.run()
    eng.reset_stats()
    ids_warm = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got_warm = eng.run()
    assert eng.stats["cached_tokens"] > 0, "repeat wave never hit the index"
    for d_, c, w_ in zip(ids_d, ids_cold, ids_warm):
        np.testing.assert_array_equal(got_cold[c], want[d_])
        np.testing.assert_array_equal(got_warm[w_], want[d_])


def test_page_sparse_composes_with_swap_restore(params):
    """Swap-restored residents (pages moved to host and back) must be
    indistinguishable to the scoring pass: overcommitted pool + swap +
    full-coverage page_topn stays bit-identical to the unpreempted dense
    baseline."""
    rng = np.random.default_rng(53)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    dense = Engine(CFG, params, _scfg(3, True))
    ids_d = [dense.submit(p, max_new_tokens=5) for p in prompts]
    want = dense.run()
    eng = Engine(CFG, params, _scfg(3, True, paged=True, page_size=8,
                                    n_pages=3, swap_pages=8, page_topn=3))
    ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = eng.run()
    assert eng.stats["swap_outs"] > 0, "pool never forced a swap: test void"
    for a, b in zip(ids_d, ids):
        np.testing.assert_array_equal(got[b], want[a])
    assert eng.allocator.in_use == 0 and eng.swap.in_use == 0


def test_page_sparse_keeps_one_prefill_one_decode_trace(params):
    """The compile-count pin survives page-sparse decode: selection and
    table compaction are traced ops inside the ONE decode trace
    (page_topn is static; prefill is untouched)."""
    eng = Engine(CFG, params, _scfg(1, True, **PAGED, page_topn=2))
    rng = np.random.default_rng(54)
    for n in (5, 8, 13, 21, 3):
        eng.submit(rng.integers(0, 64, n), max_new_tokens=3)
    eng.run()
    assert eng._step._cache_size() == 2, eng._step._cache_size()


def test_page_sparse_aggressive_touches_fewer_pages(params):
    """Aggressive page_topn: the decode-traffic counters must show
    strictly fewer pages attended (and fewer estimated KV bytes) than the
    dense walk over the same workload — the O(N*page) claim."""
    rng = np.random.default_rng(55)
    prompts = [rng.integers(0, 64, n) for n in (30, 25, 28)]
    stats = {}
    for ptn in (None, 1):
        eng = Engine(CFG, params, _scfg(3, True, **PAGED, page_topn=ptn))
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        eng.run()
        stats[ptn] = dict(eng.stats)
    assert stats[1]["decode_pages_touched"] < \
        stats[None]["decode_pages_touched"], stats
    assert stats[1]["decode_hbm_bytes"] < stats[None]["decode_hbm_bytes"], \
        stats
    # same number of decode steps -> the reduction is per-step sparsity,
    # not a shorter run
    assert stats[1]["decode_steps"] == stats[None]["decode_steps"]


def test_page_sparse_config_validation(params):
    """page_topn requires the paged cache and a positive N."""
    with pytest.raises(ValueError, match="paged"):
        Engine(CFG, params, _scfg(1, True, page_topn=2))
    with pytest.raises(ValueError, match="page_topn"):
        Engine(CFG, params, _scfg(1, True, **PAGED, page_topn=0))
