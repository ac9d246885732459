"""Model FLOP/s utilisation of the serving window: every request's
prompt forward (its real tokens, the head at the last one) and every
further served token's decode forward over its context, from shapes
(bench/counts.py), over the window and the chip's bf16 peak."""


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx["requests"]:
        return None
    m, c = ctx["model"], ctx["counts"]
    flops = 0.0
    for n_prompt, n_served in ctx["requests"]:
        flops += c.forward_flops(m, 1, n_prompt, logits_rows=1)
        for i in range(1, n_served):
            flops += c.decode_flops(m, n_prompt + i)
    return 100.0 * flops / (ctx["host_window_s"]
                            * ctx["peak"]["bf16_flops_per_s"])
