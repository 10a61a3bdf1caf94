"""Device time of gather operations (XLA gathers and fusions holding one:
under STDP the remote pre-trace gather, on a mesh the params rebuild's
sign lookup) per step, on the chip with the most (ms/step)."""
from tracereduce import GATHER, kind_time


def read(ctx):
    times = [kind_time(d, GATHER) for d in ctx.red.devices]
    if not any(times):
        return None
    return 1e3 * max(times) / ctx.steps
