"""Pallas TPU kernel: fused TeZO perturbation chain

    W ← W + scale₀·(u·diag(τ₀))·vᵀ [+ scale₁·(u·diag(τ₁))·vᵀ …]

This is the per-step hot loop of Algorithm 1.  The fusion matters on TPU
because the naive XLA lowering materializes Z = (u·diag(τ))·vᵀ in HBM (a
full parameter-sized buffer per pass); here Z never leaves VMEM — each
weight tile is loaded HBM→VMEM once, the rank-r outer product for that tile
is computed by the MXU ([bm,r]×[r,bn]), added, and stored back.  HBM traffic
drops from ~4·mn·bytes to 2·mn·bytes per pass (read+write W only; u/v tiles
are r/bn-fraction noise).

Chained transitions (τ is [k, r], scale is [k]): the perturbation-chain
step schedule (see core.zo_step) merges adjacent Algorithm-1 passes — the
restore of probe i and the perturb of probe i+1, or the final restore and
the SGD-style update — into ONE W round-trip that applies k rank-r deltas
while the tile is resident.  Each in-kernel delta ends with a cast to the
weight dtype and back to f32, reproducing bit-for-bit the rounding the
replaced HBM round-trip would have performed: the chained trajectory is
bitwise identical to the unchained one, only the HBM traffic changes.
``decay`` (the decoupled weight-decay factor 1 − lr·wd) applies to the LAST
delta only — the update touch of a restore-into-update chain; pure
perturbation deltas never decay.

Tiling: ``ops.tezo_tiles`` picks (bm, bn) per leaf from a VMEM budget — tall
blocks of 2.5–5 MiB of bf16 W at opt-13b widths (5120 × 512 on the FFN
leaves), the grid ``(cdiv(m, bm), cdiv(n, bn))`` with j innermost.  The u block
(bm, r) is fetched once per row of blocks and the v block (bn, r) once per
step, so factor bytes are r/bm of the W bytes moved.  A leaf whose dims the
block does not divide (a 50272-row vocabulary) gets a partial last block
instead of a padded copy: its out-of-bounds rows and columns are read as
unspecified values, and each output element reads only its own W element,
u row and v row, so they feed only output elements that the store drops.
input_output_aliasing keeps every leaf in place in HBM (the functional JAX
view still sees a fresh array).  The rank r is lane-aligned by ops.py
(zero-padded to a multiple of 128 on the chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fence


def _perturb_kernel(scale_ref, w_ref, u_ref, v_ref, tau_ref, o_ref, *, k, barrier):
    u = u_ref[...].astype(jnp.float32)          # [bm, r]
    v = v_ref[...].astype(jnp.float32)          # [bn, r]
    taus = tau_ref[...].astype(jnp.float32)     # [k, r]
    wf = w_ref[...].astype(jnp.float32)
    for s in range(k):
        # Bitwise contract with the standalone passes this chain replaces:
        # per-step decay rides the scalar block (1.0 on all but the final
        # update delta) rather than a compile-time literal, and each delta
        # round-trips through the VMEM output tile — the same rounding
        # barrier the replaced HBM pass had.  Interpret mode has no such
        # boundary (the ref store/load functionalizes away under jit), so
        # each delta runs inside its own fence branch with laundered
        # scalars — see kernels/fence.py for why this, and not
        # optimization_barrier, pins the rounding against the surrounding
        # schedule.  Mosaic needs none of it: its VMEM store is real.
        if barrier:
            zero = fence.data_zero(wf)
            d = scale_ref[k + s] + zero
            sc = scale_ref[s] + zero
            tau_s = taus[s : s + 1, :] + zero

            def delta(wf=wf, d=d, sc=sc, tau_s=tau_s):
                z = jax.lax.dot_general(
                    u * tau_s, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                # [bm, bn]
                return (d * wf + sc * z).astype(o_ref.dtype)

            val = fence.fenced(zero, delta, lambda wf=wf: wf.astype(o_ref.dtype))
        else:
            ut = u * taus[s : s + 1, :]          # broadcast over rows
            z = jax.lax.dot_general(
                ut, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                    # [bm, bn]
            val = (scale_ref[k + s] * wf + scale_ref[s] * z).astype(o_ref.dtype)
        o_ref[...] = val
        wf = o_ref[...].astype(jnp.float32)


# VMEM that one grid step of a TeZO pass kernel may hold (ops.tezo_tiles sizes
# the blocks to it), and the scoped VMEM limit both kernels compile with: a
# v5e core has 128 MiB, the compiler's default limit is 16 MiB.
VMEM_BUDGET = 48 << 20


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def tezo_perturb(
    w: jax.Array,       # [m, n]
    u: jax.Array,       # [m, r]
    v: jax.Array,       # [n, r]
    tau: jax.Array,     # [r] f32, or [k, r] for a k-delta chain
    scale: jax.Array | float,          # scalar, or [k] matching tau
    decay: jax.Array | float = 1.0,   # 1 − lr·wd on update touches, else 1.0
    *,
    bm: int = 256,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    m, n = w.shape
    r = u.shape[-1]
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    taus = tau.reshape((-1, r))
    k = taus.shape[0]
    scales = jnp.asarray(scale, jnp.float32).reshape(-1)
    assert scales.shape[0] in (1, k), (scales.shape, k)
    if scales.shape[0] != k:
        scales = jnp.broadcast_to(scales, (k,))
    # scalar block: [scale_0..scale_{k-1}, decay_0..decay_{k-1}] with decay
    # on the final (update) delta only — k=1 keeps the original [scale,
    # decay] layout
    decays = jnp.concatenate([
        jnp.ones((k - 1,), jnp.float32),
        jnp.asarray(decay, jnp.float32).reshape(1),
    ])
    scale_arr = jnp.concatenate([scales, decays])
    return pl.pallas_call(
        functools.partial(_perturb_kernel, k=k, barrier=interpret),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
            pl.BlockSpec((k, r), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(scale_arr, w, u, v, taus)
