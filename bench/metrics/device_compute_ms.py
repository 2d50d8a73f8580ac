"""Device time per step in which an operation that is not a collective ran
(the union of those operations' intervals), averaged over the chips."""


def read(ctx):
    return ctx.times.compute * 1e-6 / ctx.steps
