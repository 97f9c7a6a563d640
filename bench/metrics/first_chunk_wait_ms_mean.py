"""first_chunk_wait_ms_mean: mean milliseconds from a request's first
admission to the dispatch of its first prefill chunk, over the first
chunks dispatched in the window (the registry's `first_chunk_wait_s` over
`first_chunks`, `serve/engine.py`)."""


def read(run):
    n = run.counters.get("first_chunks")
    return run.counters["first_chunk_wait_s"] / n * 1e3 if n else None
