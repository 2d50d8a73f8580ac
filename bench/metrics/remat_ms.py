"""Device time per step in the forward recomputed for the backward under
``jax.checkpoint`` (``rematted_computation`` in the scope path), averaged
over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "remat")
