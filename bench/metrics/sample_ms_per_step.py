"""sample_ms_per_step: host milliseconds per pipelined step spent fetching
logits rows and drawing tokens, decode and prefill completions both (the
registry's `host_sample_s`, the `serve.sample` span in `serve/runner.py`)."""


def read(run):
    steps = run.sched["steps"]
    v = run.counters.get("host_sample_s")
    return v / steps * 1e3 if v is not None and steps else None
