"""The Mosaic kernels' share of their roofline (%): the least time of the
work they do (``leastwork``'s ``kernels`` share: local and remote delivery
of every recurrent event, the neuron update and under STDP the dense local
rule) over their device time, summed over the chips used."""
from leastwork import least_time
from tracereduce import KERNEL, kind_time


def read(ctx):
    spent = sum(kind_time(d, KERNEL) for d in ctx.red.devices) / ctx.steps
    if spent <= 0:
        return None
    t, bound = least_time(ctx.work.kernels, ctx.peak)
    ctx.note("kernel_roofline_bound", bound)
    return 100.0 * t / spent
