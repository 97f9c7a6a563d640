"""admit_wait_ms_mean: mean milliseconds from `submit()` to a request's
first admission into a slot, over the requests first admitted in the
window (the registry's `admit_wait_s` over `admitted`, `serve/engine.py`)."""


def read(run):
    n = run.counters.get("admitted")
    return run.counters["admit_wait_s"] / n * 1e3 if n else None
