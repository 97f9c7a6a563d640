"""prefill_chunk_ms: device milliseconds per execution of the prefill-chunk
program (its module event on the device)."""
from bench import devtrace, ops


def read(run):
    if run.trace is None:
        return None
    lo, hi = devtrace.window(run.trace)
    execs = ops.step_executions(run.trace, lo, hi, ops.is_prefill)
    if not execs:
        return None
    return 1e3 * sum(m.end - m.start for m, _ in execs) / len(execs)
