"""Device time per step in the model's ``attn`` blocks, forward, backward
and recomputation together, averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "attn")
