"""Device time per step in the model's ``lm_head`` (final norm, the head's
matmul and the cross-entropy), forward and backward, averaged over the
chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "lm_head")
