"""Device milliseconds per step in the forwards' ``model.attn`` scope:
norm, q/k/v, attention, o-projection and residual of every layer, over
the 2q forwards; see bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.group_ms(ctx, "attn")
