"""Device time of the neuron layer (scope ``dpsnn.neuron``: local
delivery and the LIF+SFA update, the fused column kernel) per step, on the
chip with the most (ms/step)."""
from scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "dpsnn.neuron")
