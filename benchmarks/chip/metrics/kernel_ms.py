"""Device time of the Mosaic kernels (custom calls to ``tpu_custom_call``:
the fused column step, the remote ELL delivery, under STDP also the dense
STDP update) per step, on the chip with the most (ms/step)."""
from tracereduce import KERNEL, kind_time


def read(ctx):
    times = [kind_time(d, KERNEL) for d in ctx.red.devices]
    if not any(times):
        return None
    return 1e3 * max(times) / ctx.steps
