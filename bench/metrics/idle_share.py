"""Share of the traced window in which no operation ran on the device
(1 minus the union of the operations' intervals over the window), in %,
averaged over the chips."""


def read(ctx):
    return 100.0 * (1.0 - ctx.times.busy / (ctx.hi - ctx.lo))
