"""Pallas TPU kernel: Mamba-1 selective scan with VMEM-resident state.

The recurrence  h_t = exp(Δ_t·A)∘h_t−1 + (Δ_t·x_t)·B_tᵀ ;  y_t = h_t·C_t + D∘x_t
is sequential in t and per-(channel, state) gated (A ∈ R^{D×N}), so it cannot
be chunk-parallelized like mLSTM (that trick needs per-head scalar decay —
Mamba-2/SSD territory).  The hardware answer — same as the paper's CUDA
kernel keeping state in SRAM — is to keep h in VMEM for the whole sequence:

  grid (B, D/bd); each program owns a [bd, N] state tile and loops over S
  with x/Δ/B/C resident in VMEM.  HBM traffic = read x,Δ,B,C + write y once
  (vs. the XLA scan's read+write of the full state every timestep).

VMEM at (bd=128, S≤4096, N=16): x,Δ,y tiles 3×2 MiB + B,C 2×0.25 MiB + state
8 KiB ≈ 6.5 MiB.  Longer sequences tile S via the seq grid axis (state
carries across iterations in VMEM scratch — "arbitrary" semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hl_ref,
                 h_scr, *, bs: int, ns: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)   # [bd, N]

    a = a_ref[...].astype(jnp.float32)               # [bd, N]

    def step(t, h):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)   # [bd]
        x_t = x_ref[0, t, :].astype(jnp.float32)     # [bd]
        b_t = b_ref[0, t, :].astype(jnp.float32)     # [N]
        c_t = c_ref[0, t, :].astype(jnp.float32)     # [N]
        da = jnp.exp(dt_t[:, None] * a)              # [bd, N]
        h = da * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t, :] = jnp.sum(h * c_t[None, :], axis=1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, step, h_scr[...])
    h_scr[...] = h

    @pl.when(si == ns - 1)
    def _fin():
        hl_ref[0] = h.astype(hl_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "bs", "interpret"))
def selective_scan(
    x: jax.Array,      # [B, S, D] (pre-activated conv output)
    dt: jax.Array,     # [B, S, D] (softplus'd)
    a: jax.Array,      # [D, N]    (negative)
    b: jax.Array,      # [B, S, N]
    c: jax.Array,      # [B, S, N]
    h0: jax.Array,     # [B, D, N] f32
    *,
    bd: int = 128,
    bs: int = 2048,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y [B,S,D] f32 — caller adds the D∘x skip, h_last [B,D,N])."""
    B, S, D = x.shape
    N = a.shape[-1]
    bd = min(bd, D)
    bs = min(bs, S)
    assert D % bd == 0 and S % bs == 0, (D, bd, S, bs)
    ns = S // bs
    kernel = functools.partial(_scan_kernel, bs=bs, ns=ns)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, D // bd, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((bd, N), lambda bi, di, si: (di, 0)),
            pl.BlockSpec((1, bs, N), lambda bi, di, si: (bi, si, 0)),
            pl.BlockSpec((1, bs, N), lambda bi, di, si: (bi, si, 0)),
            pl.BlockSpec((1, bd, N), lambda bi, di, si: (bi, di, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, bd, N), lambda bi, di, si: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, a, b, c, h0)
    return y, h_last
