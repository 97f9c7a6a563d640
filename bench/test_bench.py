"""CPU tests of the benchmark: the harness as data, traffic from the seed,
tail arithmetic, work counts, the chip check, and a whole tiny run whose
timed path is broken underneath.

A tiny cell (d 64, 2 layers, vocab 256) runs end to end on the CPU with the
Pallas kernels in interpret mode; the device check is skipped there.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import faults, harness, peaks, stats, traffic, work  # noqa: E402

TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True,
    "bench": {"arch": "dense_had", "registry": "granite-3-8b",
              "had": {"topn_frac": 0.117, "n_min": 16, "n_max": 4096}},
}


def _check(mix: str) -> dict:
    return harness.load_json(ROOT / "bench" / "traffic" / f"{mix}.json")["check"]


def _limit(mix: str) -> float:
    return _check(mix)["mean_gap_sd"]


def tiny_mix(loop: str) -> dict:
    serve = {"batch_slots": 2, "max_len": 512, "prefill_chunk": 32,
             "page_size": 16}
    if loop.startswith("closed"):
        # closed4: four sessions in four slots, sampled as the long-context
        # mix samples its sessions
        n = 4 if loop == "closed4" else 2
        return {"loop": "closed", "serve": {**serve, "batch_slots": n},
                "requests": n,
                "prompt_tokens": {"dist": "uniform", "lo": 40, "hi": 80},
                "output_tokens": {"dist": "fixed", "value": 400},
                "check": {**_check("longctx_decode"),
                          "sample_requests": (_check("longctx_decode")[
                              "sample_requests"] if n == 4 else 2)}}
    return {"loop": "open", "serve": serve, "rate_rps": 4.0,
            "burst": {"period_s": 2, "high_s": 0.5, "high": 3.0, "low": 0.5},
            "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                              "lo": 8, "hi": 64},
            "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                              "lo": 2, "hi": 16},
            "warmup_requests": 1,
            "check": {"sample_requests": 4, "sample_tokens": 24,
                      "mean_gap_sd": _limit("chat")}}


NEW_METRIC = '''"""tiny_tokens: tokens committed in the window (a metric added by a file)."""
from bench import stats


def read(run):
    return stats.tokens_in_window(run.requests, run.window_start,
                                  run.window_end)
'''


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    """A checkout-shaped directory holding only new files plus links to the
    benchmark's own readers, reference and adapter: a new configuration,
    two new traffic mixes, a new metric and the cells that use them."""
    root = tmp_path_factory.mktemp("tiny")
    b = root / "bench"
    for d in ("configs", "traffic", "metrics"):
        (b / d).mkdir(parents=True)
    for d in ("reference", "adapters"):
        (b / d).symlink_to(ROOT / "bench" / d)
    for f in (ROOT / "bench" / "metrics").glob("*.py"):
        (b / "metrics" / f.name).symlink_to(f)
    (b / "metrics" / "tiny_tokens.py").write_text(NEW_METRIC)
    (b / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    for loop in ("closed", "closed4", "open"):
        (b / "traffic" / f"tiny_{loop}.json").write_text(
            json.dumps(tiny_mix(loop)))
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": f"tiny.{loop}", "config": "tiny", "traffic": f"tiny_{loop}",
         "chips": 1, "why": "test"} for loop in ("closed", "closed4", "open")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    bench["end_to_end"].append({"name": "tiny_tokens", "unit": "tokens",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, workload, seed=12345678901, **kw):
    return harness.run_cell(root, workload, seed, 1.0, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            **kw)


@pytest.fixture(scope="module")
def closed_line(tiny_root):
    return _run(tiny_root, "tiny.closed", control=True)


def test_new_files_are_found_by_name(closed_line):
    m = closed_line["metrics"]
    assert m["tiny_tokens"]["value"] > 0            # a metric added as a file
    assert {"output_tok_s", "itl_p95_ms", "setup_s"} <= set(m)
    assert "ttft_p90_ms" not in m                   # closed loop: no due times
    assert list(closed_line)[-1] == "check"
    assert closed_line["device"]["platform"] == "cpu"


def test_sound_run_is_correct_and_control_is_not(closed_line):
    """The bf16 program passes; the fp8 control, read at the same prompts
    and tokens, fails the same limit."""
    c = closed_line["check"]
    assert closed_line["correct"]
    assert c["mean_gap_sd"]["value"] <= c["mean_gap_sd"]["limit"]
    assert c["control_mean_gap_sd"]["value"] > c["mean_gap_sd"]["limit"]


def test_altered_token_fails_the_check(tiny_root, monkeypatch):
    """A token altered where it is produced (the runner's sampler) makes
    the run not correct."""
    calls = faults.altered_token(monkeypatch.setattr)
    line = _run(tiny_root, "tiny.open")
    assert calls[0] > 0
    assert not line["correct"]
    c = line["check"]["mean_gap_sd"]
    assert c["value"] > c["limit"]


def test_decode_that_keeps_its_state_fails_the_check(tiny_root, monkeypatch):
    """A decode step that returns the KV pools unchanged (its new key and
    value never written) makes the run not correct."""
    faults.unchanged_state(monkeypatch.setattr)
    line = _run(tiny_root, "tiny.closed")
    c = line["check"]["mean_gap_sd"]
    assert not line["correct"] and c["value"] > c["limit"]


def test_half_the_batch_left_out_fails_the_check(tiny_root, monkeypatch):
    """Decode logits of every odd slot replaced by its even neighbour's
    (half the batch left out, filled from the rest): not correct."""
    faults.FAULTS["half_batch_odd"](monkeypatch.setattr)
    line = _run(tiny_root, "tiny.closed")
    c = line["check"]["mean_gap_sd"]
    assert not line["correct"] and c["value"] > c["limit"]


@pytest.mark.parametrize("fault", ["half_batch_odd", "half_batch_even"])
def test_either_half_of_four_slots_left_out_fails_the_check(tiny_root,
                                                            monkeypatch, fault):
    """Four sessions in four slots, sampled as the long-context mix samples
    them: breaking either half of the slots, so also the half that holds no
    longest session, makes the run not correct."""
    faults.FAULTS[fault](monkeypatch.setattr)
    line = _run(tiny_root, "tiny.closed4")
    c = line["check"]["mean_gap_sd"]
    assert not line["correct"] and c["value"] > c["limit"]


def test_long_context_check_scores_every_session():
    """The long-context mix samples every session it keeps resident."""
    mix = harness.load_json(ROOT / "bench" / "traffic" / "longctx_decode.json")
    reqs, _ = traffic.make_requests(mix, seed=2 ** 31 + 7, seconds=1.0,
                                    vocab=64)
    for r in reqs:
        r.tokens = [0]
    sample = harness.sample_for_check(reqs, mix["check"], "closed", 2 ** 31 + 7)
    assert len(sample) == len(reqs) == mix["serve"]["batch_slots"]


def test_same_seed_same_requests():
    mix = tiny_mix("open")
    a, _ = traffic.make_requests(mix, seed=2 ** 33 + 5, seconds=20, vocab=256)
    b, _ = traffic.make_requests(mix, seed=2 ** 33 + 5, seconds=20, vocab=256)
    c, _ = traffic.make_requests(mix, seed=2 ** 33 + 6, seconds=20, vocab=256)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    # another seed: the same schedule (arrivals and lengths, in order)
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in c]


def test_bursts_shape_the_arrivals():
    mix = {"rate_rps": 10.0,
           "burst": {"period_s": 10, "high_s": 2, "high": 3.0, "low": 0.5}}
    due = np.asarray(traffic.arrivals(mix, 1000.0))
    high = np.sum((due % 10) < 2) / (2 * 100)
    low = np.sum((due % 10) >= 2) / (8 * 100)
    assert abs(high - 30) < 3 and abs(low - 5) < 1


def test_tail_counts_unserved_requests():
    R = traffic.RequestRecord
    reqs = [R(0, 0.0, np.zeros(1), 1, token_times=[100.5]),
            R(1, 1.0, np.zeros(1), 1, token_times=[]),         # never served
            R(2, 2.0, np.zeros(1), 1, token_times=[130.0]),    # after the end
            R(3, 50.0, np.zeros(1), 1, token_times=[])]        # due after end
    xs = stats.ttft_samples(reqs, 100.0, 110.0)
    assert sorted(xs) == pytest.approx([0.5, 8.0, 9.0])
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([5], 90) == 5
    gaps = stats.itl_samples([R(0, 0.0, np.zeros(1), 1,
                                token_times=[99.0, 100.0, 100.25, 111.0])],
                             100.0, 110.0)
    assert gaps == [0.25]


def test_run_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "granite8b.longctx_decode", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.chip_peaks("TPU v99")
    assert peaks.chip_peaks("TPU v5 lite").flops_bf16 == 197e12


@pytest.mark.parametrize("conf,h,hk,dh,d,f", [
    ("granite-3.1-8b-4l", 32, 8, 128, 4096, 12800),
    ("phi-3-medium-128k-4l", 40, 10, 128, 5120, 17920)])
def test_work_counts_by_hand(conf, h, hk, dh, d, f):
    c = harness.load_json(ROOT / "bench" / "configs" / f"{conf}.json")
    from bench.reference import dense_had
    s = dense_had.spec_from_config(c, 4096)
    assert (s["h"], s["hk"], s["dh"], s["d"], s["f"]) == (h, hk, dh, d, f)
    # one decode call: slots at 10000 and 3000 resident keys, N = 4096
    nbytes, ops = work.paged_decode_call([10000, 3000], h=h, hk=hk, dh=dh,
                                         nsel=4096)
    k_bits = 13000 * hk * 16                 # 128 sign bits = 16 bytes a key
    v_rows = (4096 + 3000) * hk * 128 * 2    # kept keys' bf16 V rows
    q_out = 2 * h * (16 + 512)               # query bits in, f32 row out
    assert nbytes == k_bits + v_rows + q_out
    assert ops == (13000 + 7096) * h * 256
    per_layer = d * h * dh + 2 * d * hk * dh + h * dh * d + 3 * d * f
    assert work.matmul_flops_per_row(s) == 2 * 4 * per_layer
    # prefill of 5000 rows at N = 4096: kept keys saturate at N
    keys = 5000 * 5001 / 2
    kept = 4096 * 4097 / 2 + 904 * 4096
    assert work.prefill_flops(s, 5000, 4096) == pytest.approx(
        5000 * 2 * 4 * per_layer + 4 * h * 256 * (keys + kept)
        + 2 * d * c["vocab_size"])
    t, bound = work.least_time(nbytes, ops, peaks.chip_peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def _synthetic_trace():
    from bench import devtrace as D
    ops_ = [D.Op(0, "while.5", 1.0, 2.5),            # a loop spans its body
            D.Op(0, "fusion.1", 1.0, 1.5),
            D.Op(0, "paged_decode_attention.9", 1.5, 2.0),
            D.Op(0, "copy.3", 2.0, 2.5),
            D.Op(0, "prefill_attention.2", 4.0, 5.0),
            D.Op(0, "fusion.4", 5.0, 6.0)]
    modules = [D.Op(0, "jit__step(1)", 1.0, 2.5), D.Op(0, "jit__step(2)", 4.0, 6.0)]
    spans = [D.Span("window", 0.0, 10.0), D.Span("step_pipelined", 0.5, 3.0),
             D.Span("wait_arrival", 6.0, 9.0)]
    return D.Trace(ops_, modules, spans, 1)


def test_trace_reduction_by_hand():
    from bench import devtrace as D, ops as O
    tr = _synthetic_trace()
    lo, hi = D.window(tr)
    assert D.busy_seconds(tr, lo, hi) == pytest.approx(1.5 + 2.0)
    gaps = sorted(D.idle_gaps(tr, lo, hi), key=lambda g: g[1])
    assert [(g[0], g[2]) for g in gaps] == [
        ("step_pipelined", pytest.approx(1.0)), ("outside_spans", pytest.approx(1.5)),
        ("wait_arrival", pytest.approx(4.0))]
    dec = O.step_executions(tr, lo, hi, O.is_paged_decode)
    pre = O.step_executions(tr, lo, hi, O.is_prefill)
    assert [len(ops_) for _, ops_ in dec] == [4] and [len(ops_) for _, ops_ in pre] == [2]
    assert O.is_copy("copy.3") and O.is_copy("copy-start.1")
    assert O.is_copy("copy_bitcast_fusion.2") and not O.is_copy("fusion.4")
    assert O.is_copy("constant_dynamic-slice_fusion.13") and O.is_copy("slice-done.2")
    assert O.is_copy("copy_dynamic-update-slice_fusion.2") and O.is_copy("copy-done")
    assert not O.is_copy("bitcast_add_fusion.3")
    assert not O.is_copy("dynamic-slice_convert_fusion.4")
    assert not O.is_copy("convolution_bitcast_fusion")
    selfs = {o.name: s for o, s in D.self_seconds(tr.ops)}
    assert selfs["while.5"] == pytest.approx(0.0) and selfs["copy.3"] == pytest.approx(0.5)
    b = D.breakdown(tr, lo, hi, top=2)
    assert b["device_ops"][0] == ["prefill_attention.2", pytest.approx(1.0)]
    assert b["idle_gaps"][0] == ["wait_arrival", pytest.approx(4.0)]
    assert D.op_name("%copy.113 = bf16[8]{0} copy(bf16[8]{0} %x)") == "copy.113"


def test_trace_reduction_on_a_chip_trace():
    """A 1.3 s window of phi3m.chat traced on one TPU v5e (gzipped
    `.xplane.pb`, recorded once). The counts were read by hand with
    bench/trace_dump.py: 3 decode steps x 4 layers = 12 paged-decode
    kernel events (0.2227 s), 5 prefill-chunk executions."""
    from bench import devtrace as D, ops as O
    tr = D.load(str(ROOT / "bench" / "testdata" / "chat_1s.xplane.pb.gz"))
    lo, hi = D.window(tr)
    assert tr.devices == 1
    assert hi - lo == pytest.approx(1.2653386, abs=1e-6)
    busy = D.busy_seconds(tr, lo, hi)
    assert busy == pytest.approx(1.1792979, abs=1e-6) and busy < hi - lo
    kernel = [o for o in tr.ops if O.is_paged_decode(o.name) and lo <= o.start < hi]
    assert len(kernel) == 12
    assert sum(o.end - o.start for o in kernel) == pytest.approx(0.2227356, abs=1e-6)
    assert len(O.step_executions(tr, lo, hi, O.is_paged_decode)) == 3
    pre = O.step_executions(tr, lo, hi, O.is_prefill)
    assert len(pre) == 5
    assert sum(m.end - m.start for m, _ in pre) == pytest.approx(0.9122996, abs=1e-6)
    # decode-step data movement, by name: the pool slices and relayouts
    # count, arithmetic fusions do not
    moved = {}
    for _, body in O.step_executions(tr, lo, hi, O.is_paged_decode):
        for o, s in D.self_seconds(body):
            if O.is_copy(o.name):
                moved[o.name] = moved.get(o.name, 0.0) + s
    assert {"constant_dynamic-slice_fusion.13", "copy_bitcast_fusion.2",
            "copy_dynamic-update-slice_fusion.2", "copy.97",
            "slice-done.2"} <= set(moved)
    assert not {"bitcast_add_fusion.3", "fusion.142"} & set(moved)
    assert sum(moved.values()) == pytest.approx(0.0326133, abs=1e-6)
    gaps = D.idle_gaps(tr, lo, hi)
    assert sum(g[2] for g in gaps) == pytest.approx(hi - lo - busy, abs=1e-9)
    b = D.breakdown(tr, lo, hi)
    assert b["device_ops"][0][0] == "paged_decode_attention.9"
    assert b["idle_gaps"][0][0] == "wait_arrival"
