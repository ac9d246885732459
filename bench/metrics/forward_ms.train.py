"""Device milliseconds per step outside the ZO weight-pass kernels: the
2q forwards with everything that is not a pass."""
from bench import kernels
from bench.trace import seconds_matching


def read(ctx):
    if ctx.get("kind") != "zo" or ctx["steps"] <= 0:
        return None
    passes = seconds_matching(ctx["ops"], kernels.matcher(kernels.ZO_PASS))
    if passes <= 0:
        # no pass kernel found: busy less nothing would be the whole step
        return None
    return 1e3 * (ctx["busy_s"] - passes) / ctx["steps"]
