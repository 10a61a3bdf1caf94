"""The whole step's share of the chips' peak (%): the least time the
step's work needs (``leastwork``, the larger of operations over peak
FLOP/s and bytes over peak bandwidth), shared over the chips used, over
the traced wall time per step. Which bound it is goes on an earlier line."""
from leastwork import least_time


def read(ctx):
    t, bound = least_time(ctx.work.step, ctx.peak)
    ctx.note("step_mfu_bound", bound)
    per_step = ctx.window_s / ctx.steps
    return 100.0 * t / (len(ctx.red.devices) * per_step)
