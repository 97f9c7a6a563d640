"""host_exposed_ms_per_step: host milliseconds per pipelined step in which
the engine had no program in flight on the device (the registry's
`host_exposed_s`: from the return of the block on a step's logits to the
next dispatch or the end of the engine call, `serve/runner.py`)."""


def read(run):
    steps = run.sched["steps"]
    v = run.counters.get("host_exposed_s")
    return v / steps * 1e3 if v is not None and steps else None
