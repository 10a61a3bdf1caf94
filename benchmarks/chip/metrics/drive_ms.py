"""Device time of the external drive (scope ``dpsnn.drive``: Poisson
variates and drive currents) per step, on the chip with the most
(ms/step)."""
from scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "dpsnn.drive")
