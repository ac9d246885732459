"""Random weights made from the run's seed, on the device, in bfloat16.

The benchmark, not the program, makes the weights: the program under test
receives them, and the plain reference regenerates them on its own from
the same seed.  Every leaf of a layer stack is drawn per layer from
``fold_in(leaf_key, layer)``, so :func:`layer_params` regenerates one
layer alone, bit for bit what :func:`make_params` placed in the stack.

The tree has the layout of the repo's dense transformer
(``embed``, ``blocks`` stacked over layers, ``final_norm``, ``lm_head``);
the scales are that model's own spec scales (1/sqrt(d_model) for the
projections, 1/sqrt(max(d_ff, d_model)) for the down projection, 1 for
the embedding, zero norm gains).
"""
from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp


def seed31(seed: int, salt: str) -> int:
    """A 31-bit integer from any whole-number seed (which may exceed what
    ``jax.random.PRNGKey`` keeps) and a salt naming its use."""
    digest = hashlib.sha256(f"{salt}:{int(seed)}".encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def block_shapes(m: dict) -> dict:
    """name -> (per-layer shape, init scale or None for zeros)."""
    D, H, KV, dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    s_attn = 1.0 / math.sqrt(D)
    s_ff = 1.0 / math.sqrt(max(F, D))
    shapes = {
        "ln1": ((D,), None),
        "wq": ((D, H * dh), s_attn),
        "wk": ((D, KV * dh), s_attn),
        "wv": ((D, KV * dh), s_attn),
        "wo": ((H * dh, D), s_attn),
        "ln2": ((D,), None),
    }
    if m["activation"] != "gelu":
        shapes["w_gate"] = ((D, F), s_attn)
    shapes["w_up"] = ((D, F), s_attn)
    shapes["w_down"] = ((F, D), s_ff)
    return shapes


def weights_key(seed: int):
    """The key every weight of a run's seed is drawn from; pass it to the
    functions below as a traced argument, so one compiled program serves
    every seed."""
    return jax.random.PRNGKey(seed31(seed, "weights"))


def _leaf_key(key, name: str):
    return jax.random.fold_in(
        key,
        int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "little")
        & 0x7FFFFFFF,
    )


def _draw(key, shape, scale, dtype):
    if scale is None:
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def layer_leaf(m: dict, key, layer, name: str):
    """One layer's slice of the block leaf ``name`` (``layer`` may be
    traced)."""
    shape, scale = block_shapes(m)[name]
    return _draw(jax.random.fold_in(_leaf_key(key, name), layer), shape,
                 scale, jnp.dtype(m["dtype"]))


def layer_params(m: dict, key, layer) -> dict:
    """One layer's block weights (``layer`` may be traced)."""
    return {name: layer_leaf(m, key, layer, name) for name in block_shapes(m)}


def outer_params(m: dict, key) -> dict:
    """embed, final_norm and lm_head."""
    D, V = m["d_model"], m["vocab_size"]
    dt = jnp.dtype(m["dtype"])
    return {
        "embed": _draw(_leaf_key(key, "embed"), (V, D), 1.0, dt),
        "final_norm": jnp.zeros((D,), dt),
        "lm_head": _draw(_leaf_key(key, "lm_head"), (D, V),
                         1.0 / math.sqrt(D), dt),
    }


def make_params(m: dict, key) -> dict:
    """The whole tree, layers stacked; call under one ``jax.jit``."""
    blocks = jax.vmap(lambda l: layer_params(m, key, l))(
        jnp.arange(m["n_layers"])
    )
    return {**outer_params(m, key), "blocks": blocks}
