"""Device time per step in the forward pass: the union of the leaf
operations under the program's ``fwd`` scope as ``jax.value_and_grad`` names
it (``jvp(fwd)``, no ``transpose(``), averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "fwd")
