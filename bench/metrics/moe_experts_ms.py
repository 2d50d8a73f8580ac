"""Device time per step under the held experts' ``moe_experts`` scope
(``models/moe.moe_ffn_held``), forward, backward and recomputation
together, averaged over the chips."""
from bench import named_scopes


def read(ctx):
    return named_scopes.named_ms(ctx, "moe_experts")
