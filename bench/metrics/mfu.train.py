"""Model FLOP/s utilisation of the whole ZO step over the traced window:
the 2q forwards' operations from shapes (bench/counts.py) times the steps
completed, over the window and the chip's bf16 peak."""


def read(ctx):
    if ctx.get("kind") != "zo" or ctx["steps"] <= 0:
        return None
    m, t, c = ctx["model"], ctx["traffic"], ctx["counts"]
    flops = c.zo_step_flops(m, t["batch"], t["seq"], t["q_probes"])
    return 100.0 * flops * ctx["steps"] / (
        ctx["host_window_s"] * ctx["peak"]["bf16_flops_per_s"])
