"""Device time of the ops of no named scope (counters, checksums, the
integrity guard, copies, ops XLA adds with no ``op_name``), loops left out,
per step, on the chip with the most (ms/step)."""
from scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, None)
