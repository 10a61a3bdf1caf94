"""Device time of collective operations (the halo exchange's
collective-permutes, the counters' all-reduces) per step, on the chip
with the most (ms/step)."""
from tracereduce import COLLECTIVE, kind_time


def read(ctx):
    times = [kind_time(d, COLLECTIVE) for d in ctx.red.devices]
    if not any(times):
        return None
    return 1e3 * max(times) / ctx.steps
