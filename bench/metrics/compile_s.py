"""Seconds to compile the cell's step in set-up (AOT ``lower().compile()``,
a hit of the persistent compilation cache after the first run)."""


def read(ctx):
    return ctx.compile_s
