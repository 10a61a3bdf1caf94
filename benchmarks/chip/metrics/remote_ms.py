"""Device time of remote delivery (scope ``dpsnn.remote``: packing the
spike table into words and the ELL delivery kernel) per step, on the chip
with the most (ms/step)."""
from scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "dpsnn.remote")
