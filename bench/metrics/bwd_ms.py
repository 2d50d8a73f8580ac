"""Device time per step in the backward pass: the union of the leaf
operations under ``transpose(jvp(fwd))`` that are not a recomputed forward
(``rematted_computation``), averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "bwd")
