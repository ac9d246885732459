"""Device milliseconds per step in the step's ``zo.update`` scope: the
restore folded into the optimizer's fused update; see bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.group_ms(ctx, "update")
