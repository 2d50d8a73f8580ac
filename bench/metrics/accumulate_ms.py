"""Device time per step in the program's ``accumulate`` scope: the gradient's
flatten into the parameter space, the microbatch sum and its mean,
averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "accumulate")
