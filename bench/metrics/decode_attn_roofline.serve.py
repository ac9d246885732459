"""Share of the paged decode-attention roofline that its kernel reaches:
for every decode position of every request, the live keys and values it
reads (bench/counts.py, no padding), at the HBM and bf16 peaks, over the
device time of the paged decode kernel."""
from bench import kernels
from bench.trace import seconds_matching


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    s = seconds_matching(ctx["ops"], kernels.matcher(kernels.PAGED_DECODE))
    if s <= 0:
        return None
    m, c = ctx["model"], ctx["counts"]
    flops = bytes_ = 0.0
    for n_prompt, n_served in ctx["requests"]:
        for i in range(1, n_served):
            f, b = c.decode_attention_cost(m, n_prompt + i)
            flops += f
            bytes_ += b
    return 100.0 * c.least_seconds(flops, bytes_, ctx["peak"]) / s
