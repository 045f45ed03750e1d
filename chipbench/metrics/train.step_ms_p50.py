"""Median device time of the compiled train step, from the trace: the
duration of the step's program on the chip's ``XLA Modules`` line."""
from chipbench import stats, trace_reduce

STEP_PROGRAM = r"^train_step$"


def read(ctx):
    durs = trace_reduce.module_durations(ctx["trace"], STEP_PROGRAM)
    return 1e3 * stats.median(durs) if durs else None
