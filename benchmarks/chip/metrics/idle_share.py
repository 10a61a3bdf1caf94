"""Share of the traced window in which no operation ran on the device,
mean over the chips used (%). Each chip's share is printed on an earlier
line of standard error."""
from tracereduce import busy


def read(ctx):
    shares = [100.0 * (1.0 - busy(d) / ctx.window_s) for d in ctx.red.devices]
    ctx.note("idle_share_per_chip", shares)
    return sum(shares) / len(shares)
