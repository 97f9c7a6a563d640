"""setup_s: seconds from process start to the window's start (loading,
weights, engine, compilation or cache loads, warm-up and set-up traffic)."""


def read(run):
    return run.setup_s
