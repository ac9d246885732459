"""Device milliseconds per step in the forwards' ``model.embed`` and
``model.head`` scopes: the input embedding, and the final norm, the
lm_head logits and the cross-entropy; see bench/scopes.py."""
from bench import scopes


def read(ctx):
    return scopes.group_ms(ctx, "head")
