"""Share of the traced training window in which no operation ran on the
device, averaged over the chips used."""


def read(ctx):
    if ctx.get("kind") != "zo" or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
