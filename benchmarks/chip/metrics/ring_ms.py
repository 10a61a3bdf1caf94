"""Device time of the spike ring (scope ``dpsnn.ring``: the delayed spike
table, the history's reads and writes) per step, on the chip with the
most (ms/step)."""
from scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "dpsnn.ring")
