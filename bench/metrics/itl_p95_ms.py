"""itl_p95_ms: 95th percentile over all gaps between consecutive output
tokens of any request, where both tokens were committed in the window."""
from bench import stats


def read(run):
    v = stats.percentile(stats.itl_samples(run.requests, run.window_start,
                                           run.window_end), 95)
    return None if v is None else v * 1e3
