"""Device milliseconds per step in the ZO weight-pass kernels."""
from bench import kernels
from bench.trace import seconds_matching


def read(ctx):
    if ctx.get("kind") != "zo" or ctx["steps"] <= 0:
        return None
    s = seconds_matching(ctx["ops"], kernels.matcher(kernels.ZO_PASS))
    return 1e3 * s / ctx["steps"] if s > 0 else None
