"""Device time per step in the exchange's ``push`` scope: the gradient's
reduce-scatter (or all-reduce) and its mean, averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "push")
