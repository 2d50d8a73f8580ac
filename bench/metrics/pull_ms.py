"""Device time per step in the program's ``pull`` scope: the exchange's
casts and all-gather of the updated slabs, and the trainer's layout of the
pulled parameters (their reshape into the state's row, and their unflatten
into the model's tree at the start of the next step), averaged over the
chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "pull")
