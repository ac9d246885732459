"""Device milliseconds per step under no program scope (the scalar kappa
arithmetic, copies the compiler adds); with the five scoped groups it
partitions the busy time.  Reads 0.0 when every operation is scoped; see
bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.unscoped_ms(ctx)
