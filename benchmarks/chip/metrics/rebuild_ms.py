"""Device time of the params rebuild (scope ``dpsnn.params``: synapse
generation inside a call that rebuilds them, as the mesh's resume does)
per step, on the chip with the most (ms/step)."""
from scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "dpsnn.params")
