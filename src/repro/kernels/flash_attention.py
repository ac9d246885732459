"""Pallas TPU kernel: forward-only FlashAttention (causal, GQA, sliding
window).

ZO fine-tuning is 100% forward passes, so the forward attention kernel is the
compute hot-spot of the whole system (the dry-run's memory term is dominated
by materialized S×T score buffers in the XLA path).  Online-softmax tiling
keeps the score block (bq×bk f32) in VMEM.

Canonical TPU accumulation pattern: grid = (B, nq, nk) with the kv-block
index innermost ("arbitrary" dimension semantics ⇒ sequential on TPU);
running (m, l, acc) live in VMEM scratch across the nk iterations and the
output tile is written on the last one.  Fully-masked blocks (above the
causal diagonal / outside the sliding window) still iterate but skip the
matmuls via @pl.when.

Each block carries every (local) head — ``(1, bq, H, dh)`` over
``[B, S, H, dh]`` — and the kernel loops over heads: Mosaic accepts a
block's second-minor dim only whole or in multiples of 8, so a one-head
block over H = 12 is refused, and carrying all heads keeps the model's
[B, S, H, dh] layout with no HBM transpose.

VMEM working set grows with bq·H (the block's minor dims pad to (16, 128)
tiles in bf16, (8, 128) in f32), so the default tile is 128: at H = 12,
dh = 64 the double-buffered q/k/v/o tiles, their f32 copies and the f32
(m, l, acc) scratch stay inside the 16 MiB scoped-VMEM default; at 512 they
do not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bq: int, bk: int, nk: int, scale: float, group: int,
    causal: bool, window: int, q_offset: int, kv_len: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # kv_len masks the zero-padded kv tail when T was padded up to the tile
    # multiple (pad-and-mask tiling for awkward sequence lengths)
    allow = kpos < kv_len
    if causal:
        allow = allow & (kpos <= qpos)
    if window > 0:
        allow = allow & (qpos - kpos < window)

    # cheap block-level skip: block is live iff its corner positions overlap
    q_lo = iq * bq + q_offset
    q_hi = q_lo + bq - 1
    k_lo = ik * bk
    k_hi = k_lo + bk - 1
    live = jnp.asarray(k_lo < kv_len)
    if causal:
        live = live & (k_lo <= q_hi)
    if window > 0:
        live = live & (q_lo - k_hi < window)

    @pl.when(live)
    def _body():
        # whole [b, H, dh] tiles widen to f32 first: Mosaic slices a head out
        # of the second-minor dim of an f32 value, not of a packed bf16 ref
        qa = q_ref[0].astype(jnp.float32)               # [bq, H, dh]
        ka = k_ref[0].astype(jnp.float32)               # [bk, KV, dh]
        va = v_ref[0].astype(jnp.float32)
        for h in range(qa.shape[1]):
            q, k, v = qa[:, h, :], ka[:, h // group, :], va[:, h // group, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale                                   # [bq, bk]
            s = jnp.where(allow, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_scr[h] = m_new

    @pl.when(ik == nk - 1)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)          # [H, bq, 1]
        o = acc_scr[...] / denom                        # [H, bq, dh]
        o_ref[0] = jnp.stack(
            [o[h] for h in range(o.shape[0])], axis=1
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "bq", "bk", "kv_len", "head_scale",
        "interpret",
    ),
)
def flash_attention(
    q: jax.Array,        # [B, S, H, dh]
    k: jax.Array,        # [B, T, KV, dh]
    v: jax.Array,        # [B, T, KV, dh]
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    kv_len: int = 0,
    head_scale: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """``kv_len`` (0 ≡ T) is the true kv length when T carries zero-padding
    from the pad-and-mask tiling; ``head_scale`` (0 ≡ dh**-0.5) pins the
    softmax scale to the *unpadded* head dim when dh was lane-padded."""
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    nq, nk = S // bq, T // bk
    scale = head_scale if head_scale else dh ** -0.5
    kv_len = kv_len or T

    kernel = functools.partial(
        _flash_kernel,
        bq=bq, bk=bk, nk=nk, scale=scale, group=G,
        causal=causal, window=window, q_offset=q_offset, kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, H, dh), lambda b, iq, ik: (b, iq, 0, 0)),
            pl.BlockSpec((1, bk, KV, dh), lambda b, iq, ik: (b, ik, 0, 0)),
            pl.BlockSpec((1, bk, KV, dh), lambda b, iq, ik: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, H, dh), lambda b, iq, ik: (b, iq, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((H, bq, 1), jnp.float32),
            pltpu.VMEM((H, bq, 1), jnp.float32),
            pltpu.VMEM((H, bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
