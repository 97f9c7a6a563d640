"""CPU test of the metrics that read the serving engine's step counters: a
traced run of the tiny open-loop cell yields each of them, and the kept
trace holds the engine's `serve.*` spans inside the benchmark's
`step_pipelined` calls."""
from __future__ import annotations

import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import devtrace, harness  # noqa: E402
from bench.test_bench import tiny_root  # noqa: E402,F401  (fixture)

STEP_METRICS = ("host_exposed_ms_per_step", "sample_ms_per_step",
                "prefill_row_use_pct", "admit_wait_ms_mean",
                "first_chunk_wait_ms_mean")


def test_traced_run_reads_the_step_counters(tiny_root, tmp_path):
    """A traced run of the open mix yields each metric the engine's step
    counters feed, and its kept trace holds the engine's `serve.*` spans
    inside the benchmark's `step_pipelined` calls."""
    from jax.profiler import ProfileData
    line = harness.run_cell(tiny_root, "tiny.open", 2 ** 31 + 99, 1.0, True,
                            t_start=time.perf_counter(), require_tpu=False,
                            keep_trace=str(tmp_path))
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(STEP_METRICS) <= set(m), sorted(m)
    assert 0 < m["prefill_row_use_pct"] < 100           # padded rows
    assert m["sample_ms_per_step"] > 0 and m["admit_wait_ms_mean"] > 0
    assert m["host_exposed_ms_per_step"] > 0
    assert m["first_chunk_wait_ms_mean"] >= 0
    pd = ProfileData.from_file(devtrace.find_xplane(str(tmp_path)))
    names = {ev.name for plane in pd.planes if plane.name.startswith("/host:")
             for line_ in plane.lines for ev in line_.events}
    assert {"step_pipelined", "serve.schedule", "serve.land",
            "serve.sample", "serve.decode", "serve.prefill_chunk"} <= names
