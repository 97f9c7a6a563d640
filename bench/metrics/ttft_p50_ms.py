"""ttft_p50_ms: 50th percentile over every request due in the window of
first-token time minus due time; unserved requests enter with their wait
so far."""
from bench import stats


def read(run):
    if run.mix["loop"] != "open":
        return None
    xs = stats.ttft_samples(run.requests, run.window_start, run.window_end)
    v = stats.percentile(xs, 50)
    return None if v is None else v * 1e3
