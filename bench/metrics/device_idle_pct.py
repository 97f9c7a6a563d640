"""device_idle_pct: 100 x (1 - union of device-op intervals / traced
window), averaged over the chips used."""
from bench import devtrace


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = devtrace.window(run.trace)
    return 100.0 * (1.0 - devtrace.busy_seconds(run.trace, lo, hi) / (hi - lo))
