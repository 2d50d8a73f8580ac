"""Host time per step spent waiting in ``next()`` of the program's
``Prefetcher``, from the benchmark's ``input`` spans in the traced window."""


def read(ctx):
    return ctx.input_s / ctx.steps * 1e3
