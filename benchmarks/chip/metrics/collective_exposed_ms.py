"""The part of the collectives' device time during which no other
operation runs on that chip, per step, on the chip with the most
(ms/step)."""
from tracereduce import COLLECTIVE, exposed, kind_time


def read(ctx):
    if not any(kind_time(d, COLLECTIVE) for d in ctx.red.devices):
        return None
    return 1e3 * max(exposed(d, COLLECTIVE) for d in ctx.red.devices) / (
        ctx.steps)
