"""step_mfu: model FLOPs of the rows served in the window over window
seconds times the bf16 peak (bench/mfu.py), in percent."""
from bench import mfu


def read(run):
    return mfu.step_mfu(run)
