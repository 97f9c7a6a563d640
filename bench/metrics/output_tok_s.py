"""output_tok_s: output tokens committed inside the window per second of
window."""
from bench import stats


def read(run):
    n = stats.tokens_in_window(run.requests, run.window_start, run.window_end)
    return n / run.window_s if n else None
