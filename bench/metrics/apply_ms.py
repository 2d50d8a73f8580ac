"""Device time per step in the exchange's ``apply`` scope: the optimizer
update of the owned slab, averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "apply")
