"""Plain float32 forward pass of the dense transformer the benchmark runs.

Written from the layer equations, not from the program: pre-norm blocks
of RMSNorm (gain ``1 + scale``), multi-head or grouped-query causal
attention with half-rotation RoPE, and either a two-matrix tanh-GELU FFN
or a SwiGLU FFN; a final RMSNorm, an untied head and the mean token
cross-entropy.  Every matrix product runs at ``Precision.HIGHEST``.

``precision="fp8"`` is the control: the same arithmetic, but every
operand of every matrix product first rounded to three mantissa bits
(the e4m3 mantissa, with per-tensor scaling assumed so that no value
leaves its range), as an fp8 forward would compute it.  A comparison
that cannot tell it from the program is too loose.

Weights arrive as bfloat16 arrays, one layer at a time; each layer is
upcast inside its own jitted call, so only one layer's float32 copy is
ever live.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def round_fp8(x: jax.Array) -> jax.Array:
    """Round float32 to the nearest value with three mantissa bits (ties
    to even), keeping the float32 exponent."""
    b = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    lsb = (b >> 20) & jnp.uint32(1)
    b = (b + jnp.uint32(0x7FFFF) + lsb) & jnp.uint32(0xFFF00000)
    return lax.bitcast_convert_type(b, jnp.float32)


def _operand(x, precision):
    x = x.astype(jnp.float32)
    return round_fp8(x) if precision == "fp8" else x


def mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=HIGHEST)


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def rope(x, theta):
    """x [B, S, N, dh]: rotate the two halves of each head by position."""
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    s, c = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, precision):
    """Causal attention; q [B,S,H,dh], k/v [B,S,KV,dh]."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, dh)
    s = mm("bskgd,btkd->bkgst", qg, k, precision) * dh ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bkgst,btkd->bskgd", p, v, precision)
    return o.reshape(B, S, H * dh)


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def block(x, p, ln1, ln2, *, m, precision):
    """One layer: x [B, S, D] float32 -> float32.  ``m`` is the model
    dictionary as a hashable tuple of items."""
    m = dict(m)
    B, S, _ = x.shape
    H, KV, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    h = rms_norm(x, ln1, eps)
    q = mm("bsd,de->bse", h, p["wq"], precision).reshape(B, S, H, dh)
    k = mm("bsd,de->bse", h, p["wk"], precision).reshape(B, S, KV, dh)
    v = mm("bsd,de->bse", h, p["wv"], precision).reshape(B, S, KV, dh)
    o = attention(rope(q, theta), rope(k, theta), v, precision)
    x = x + mm("bse,ed->bsd", o, p["wo"], precision)
    h = rms_norm(x, ln2, eps)
    up = mm("bsd,df->bsf", h, p["w_up"], precision)
    if m["activation"] == "gelu":
        a = jax.nn.gelu(up, approximate=True)
    elif m["activation"] == "swiglu":
        a = jax.nn.silu(mm("bsd,df->bsf", h, p["w_gate"], precision)) * up
    else:
        raise ValueError(f"unknown activation {m['activation']!r}")
    return x + mm("bsf,fd->bsd", a, p["w_down"], precision)


@jax.jit
def embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def head_logits(x, final_norm, lm_head, *, eps, precision):
    """Final norm and logits, float32 [..., V]."""
    return mm("...d,dv->...v", rms_norm(x, final_norm, eps), lm_head,
              precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _nll_sum(x, targets, final_norm, lm_head, *, eps, precision):
    logits = head_logits(x, final_norm, lm_head, eps=eps, precision=precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def hidden(m: dict, layers, ln1, ln2, embed_table, tokens, precision="f32"):
    """Final-layer hidden states [B, S, D] (float32), layer by layer.
    ``layers`` yields each layer's weight dict (it may make them lazily)."""
    mt = tuple(sorted(m.items()))
    x = embed(embed_table, tokens)
    for l, p in enumerate(layers):
        x = block(x, p, ln1[l], ln2[l], m=mt, precision=precision)
    return x


def loss(m: dict, layers, ln1, ln2, outer, tokens, targets, precision="f32",
         rows_per_chunk: int = 2):
    """Mean next-token cross-entropy over every position (float32)."""
    x = hidden(m, layers, ln1, ln2, outer["embed"], tokens, precision)
    total = jnp.zeros((), jnp.float32)
    for r in range(0, x.shape[0], rows_per_chunk):
        total = total + _nll_sum(
            x[r:r + rows_per_chunk], targets[r:r + rows_per_chunk],
            outer["final_norm"], outer["lm_head"],
            eps=m["norm_eps"], precision=precision,
        )
    return total / targets.size
