"""gen_lag_p90_ms: 90th percentile of how late the load generator submitted
each request due in the window (actual submit minus due time)."""
from bench import stats


def read(run):
    if run.mix["loop"] != "open":
        return None
    lags = [r.submitted - (run.window_start + r.due) for r in run.requests
            if r.submitted is not None and r.submitted >= run.window_start]
    v = stats.percentile(lags, 90)
    return None if v is None else v * 1e3
