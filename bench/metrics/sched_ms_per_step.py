"""sched_ms_per_step: host milliseconds the scheduler spent building a plan,
per pipelined step in the window (the engine's own schedule-phase timer,
`Engine.overlap_stats()`)."""


def read(run):
    steps = run.sched["steps"]
    return run.sched["schedule_s"] / steps * 1e3 if steps else None
