"""Device time per step in collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, by the compiler's op kind),
averaged over the chips. Nothing to read where the step holds none."""


def read(ctx):
    if ctx.times.collective <= 0:
        return None
    return ctx.times.collective * 1e-6 / ctx.steps
