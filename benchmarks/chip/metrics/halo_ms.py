"""Device time of the halo exchange (scope ``dpsnn.halo``: packing, the
collective-permutes, unpacking) per step, on the chip with the most
(ms/step)."""
from scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "dpsnn.halo")
