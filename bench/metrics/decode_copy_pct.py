"""decode_copy_pct: device self time of data-movement ops (copies, pool
slices and relayouts: `ops.is_copy`) inside the decode-step program, over
that program's device time, in percent."""
from bench import devtrace, ops


def read(run):
    if run.trace is None:
        return None
    lo, hi = devtrace.window(run.trace)
    execs = ops.step_executions(run.trace, lo, hi, ops.is_paged_decode)
    total = sum(m.end - m.start for m, _ in execs)
    copy = sum(s for _, body in execs
               for o, s in devtrace.self_seconds(body) if ops.is_copy(o.name))
    return 100.0 * copy / total if total else None
