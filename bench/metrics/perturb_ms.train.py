"""Device milliseconds per step in the step's perturb scopes: ``zo.begin``
(TeZO's factor draw), ``zo.perturb`` (the first perturb, and each bridge
when q > 1) and ``zo.flip`` (the -2 rho perturb); see bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.group_ms(ctx, "perturb")
