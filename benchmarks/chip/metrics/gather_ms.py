"""Device time of gather operations (XLA gathers and fusions holding one:
remote ELL delivery, and under STDP the remote pre-trace gather) per
step, on the chip with the most (ms/step)."""
from tracereduce import GATHER, kind_time


def read(ctx):
    times = [kind_time(d, GATHER) for d in ctx.red.devices]
    if not any(times):
        return None
    return 1e3 * max(times) / ctx.steps
