"""Device time per step in collective operations during which no other
operation ran on that chip, averaged over the chips. Nothing to read where
the step holds no collective."""


def read(ctx):
    if ctx.times.collective <= 0:
        return None
    return ctx.times.exposed * 1e-6 / ctx.steps
