"""Share of the ZO weight passes' roofline that their kernels reach: the
least time of the 2q+1 passes from shapes (bench/counts.py: bytes at the
HBM peak, operations at the bf16 peak, the larger) over the device time
of the pass kernels."""
from bench import kernels
from bench.trace import seconds_matching


def read(ctx):
    if ctx.get("kind") != "zo" or ctx["steps"] <= 0:
        return None
    s = seconds_matching(ctx["ops"], kernels.matcher(kernels.ZO_PASS))
    if s <= 0:
        return None
    m, t, c = ctx["model"], ctx["traffic"], ctx["counts"]
    cost = c.zo_pass_cost(m, t["q_probes"], t["rank"])
    least = c.least_seconds(cost["flops"], cost["bytes"], ctx["peak"])
    return 100.0 * least * ctx["steps"] / s
