"""Device time of plasticity (scope ``dpsnn.stdp``: the pre-trace table,
the remote pre-trace gather and rule, the dense STDP kernel) per step, on
the chip with the most (ms/step)."""
from scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "dpsnn.stdp")
