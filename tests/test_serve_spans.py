"""Spans and host counters inside the serving step (serve/engine.py,
serve/runner.py).

Every step phase runs under a `serve.*` profiler span, and the registry
counts the host's exposed and sampling time, the prefill rows computed
and the waits to first admission and first chunk. The spans and counters
are observers: outputs stay bit-identical to the sync loop, and the
1-prefill + 1-decode trace pin holds.
"""
import glob
import time

import jax
import numpy as np
import pytest

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serve import Engine, SamplingParams, ServeConfig

CFG = ModelConfig(name="spans", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                  head_dim=16, param_dtype="float32", q_block=16,
                  remat=False)

# every span the engine and runner open, with the metadata each carries
SPANS = {"serve.schedule": {"step"}, "serve.land": {"step"},
         "serve.device_wait": set(), "serve.sample": {"slots"},
         "serve.commit": set(), "serve.resolve": set(),
         "serve.dispatch": set(), "serve.swap": {"pages"},
         "serve.prefill_chunk": {"request_id", "lo", "hi"},
         "serve.decode": {"slots"}, "serve.commit_structural": set()}

# paged, prefix cache and a swap pool, with too few pages for the load
OVERCOMMIT = dict(paged=True, page_size=4, n_pages=9, prefix_cache=True,
                  swap_pages=32)


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.PRNGKey(10), CFG)


def _scfg(**kw):
    return ServeConfig(batch_slots=2, max_len=48, prefill_chunk=8,
                       binary=True, topn=6, **kw)


def _submit(eng):
    rng = np.random.default_rng(42)
    return [eng.submit(rng.integers(1, 64, n).astype(np.int32),
                       max_new_tokens=6 + (k % 3),
                       sampling=SamplingParams(temperature=0.8, top_k=8,
                                               seed=k))
            for k, n in enumerate((11, 7, 19, 5, 13, 9))]


def _timed_run(eng, step):
    """Drain the engine one call at a time; returns (outputs, summed wall
    seconds of the engine calls)."""
    out, wall = {}, 0.0
    while (eng.queue or any(s.request is not None for s in eng.slots)
           or eng._inflight is not None):
        t = time.perf_counter()
        finished = step()
        wall += time.perf_counter() - t
        for fr in finished:
            out[fr.request_id] = fr.tokens
    for fr in eng.scheduler._drain_finished():
        out[fr.request_id] = fr.tokens
    return out, wall


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """An overcommitted pipelined run under the profiler: its outputs, its
    engine, the summed wall time of its engine calls, and the host events
    of its trace."""
    from jax.profiler import ProfileData
    eng = Engine(CFG, params, _scfg(**OVERCOMMIT))
    ids = _submit(eng)
    d = str(tmp_path_factory.mktemp("prof"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        out, wall = _timed_run(eng, eng.step_pipelined)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[-1]
    events = [(ev.name, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("serve.")]
    return {"out": [out[r] for r in ids], "eng": eng, "wall": wall,
            "events": events}


def test_every_span_is_on_a_host_line(traced):
    assert traced["eng"].stats["swap_outs"] > 0     # serve.swap ran
    seen = {}
    for name, meta in traced["events"]:
        seen.setdefault(name, []).append(meta)
    assert set(seen) == set(SPANS), sorted(seen)
    for name, keys in SPANS.items():
        assert all(keys <= set(m) for m in seen[name]), (name, seen[name][:3])
    chunks = seen["serve.prefill_chunk"]
    assert len(chunks) == traced["eng"].stats["prefill_chunks"]
    assert {m["request_id"] for m in chunks} == set(range(6))
    assert all(0 <= m["lo"] < m["hi"] for m in chunks)


def test_spans_change_no_token(params, traced):
    """The traced pipelined run is bit-identical to the untraced sync
    loop, and the trace pin holds."""
    ref = Engine(CFG, params, _scfg(**OVERCOMMIT))
    ids = _submit(ref)
    out = ref.run()
    for got, rid in zip(traced["out"], ids):
        np.testing.assert_array_equal(got, out[rid])
    assert traced["eng"]._step._cache_size() == 2


@pytest.mark.parametrize("pipelined", [False, True])
def test_host_time_counters_lie_inside_engine_calls(params, pipelined):
    eng = Engine(CFG, params, _scfg(**OVERCOMMIT))
    _submit(eng)
    _, wall = _timed_run(eng, eng.step_pipelined if pipelined else eng.step)
    st = eng.stats
    assert 0 < st["host_sample_s"] < wall
    assert 0 < st["host_exposed_s"] < wall
    if pipelined:
        assert 0 < st["host_overlap_s"] <= st["host_schedule_s"] < wall
    else:
        assert st["host_schedule_s"] == st["host_overlap_s"] == 0


def test_traced_host_counters_lie_inside_engine_calls(traced):
    st = traced["eng"].stats
    assert 0 < st["host_sample_s"] < traced["wall"]
    assert 0 < st["host_exposed_s"] < traced["wall"]


def test_prefill_rows_count_padding(params):
    eng = Engine(CFG, params, _scfg(paged=True, page_size=4))
    _submit(eng)
    eng.run_pipelined()
    st = eng.stats
    assert st["prefill_rows"] == st["prefill_chunks"] * 2 * 8
    assert 0 < st["prefill_tokens"] < st["prefill_rows"]


@pytest.mark.parametrize("swap_pages", [0, 32])
def test_first_admissions_counted_once(params, swap_pages):
    """Every request is counted once at its first admission and once at
    its first chunk, whatever preemptions (recompute replay, or swap)
    re-admit it later."""
    eng = Engine(CFG, params, _scfg(paged=True, page_size=4, n_pages=9,
                                    swap_pages=swap_pages))
    ids = _submit(eng)
    out = eng.run_pipelined()
    st = eng.stats
    assert st["preemptions"] > 0, "pool never pressured: test is void"
    if swap_pages:
        assert st["swap_outs"] > 0
    else:
        assert st["replayed_tokens"] > 0           # recompute preemption
    assert len(out) == len(ids)
    assert st["admitted"] == st["first_chunks"] == len(ids)
    assert st["admit_wait_s"] > 0 and st["first_chunk_wait_s"] >= 0
    assert not eng._submitted and not eng._admitted


def test_overlap_stats_read_the_registry(params):
    eng = Engine(CFG, params, _scfg(**OVERCOMMIT))
    _submit(eng)
    eng.run_pipelined()
    ov, st = eng.overlap_stats(), eng.stats
    assert ov["schedule_s"] == st["host_schedule_s"] > 0
    assert ov["overlap_s"] == st["host_overlap_s"]
    assert ov["pipelined_steps"] == st["pipelined_steps"] > 0
    eng.reset_stats()
    assert eng.overlap_stats() == {"schedule_s": 0, "overlap_s": 0,
                                   "pipelined_steps": 0, "overlap_frac": 0.0}


@pytest.mark.parametrize("page_topn", [None, 2])
def test_decode_page_counters_follow_residency(params, page_topn):
    """Each decode step lists every slot's table row, max_len / page = 12
    pages (page_topn of them when compacted), and walks the
    ceil((pos + 1) / page) that hold tokens, empty slots included."""
    eng = Engine(CFG, params, _scfg(paged=True, page_size=4,
                                    page_topn=page_topn))
    seen = []
    decode_step = eng.runner.decode_step

    def spy(tokens, pos, *args, **kw):
        seen.append(np.array(pos))
        return decode_step(tokens, pos, *args, **kw)

    eng.runner.decode_step = spy
    for n, gen in ((5, 3), (21, 6)):
        eng.submit(np.arange(1, n + 1, dtype=np.int32), max_new_tokens=gen)
    eng.run()
    listed = 12 if page_topn is None else page_topn
    st = eng.stats
    assert len(seen) == st["decode_steps"] > 0
    assert st["decode_pages_listed"] == listed * 2 * len(seen)
    assert st["decode_pages_walked"] == sum(
        np.minimum(-(-(p + 1) // 4), listed).sum() for p in seen)
    assert 0 < st["decode_pages_walked"] < st["decode_pages_listed"]
