"""Device milliseconds per request in the engine's prefill executables
(the modules of ``prefill_paged``, every bucket)."""


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx["requests"]:
        return None
    s = sum(v for k, v in ctx["modules"].items() if "prefill_paged" in k)
    return 1e3 * s / len(ctx["requests"]) if s > 0 else None
