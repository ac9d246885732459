"""Pallas TPU kernel: fused TeZO-Adam update

    W ← W − lr · M / √(V + ε),
    M = (u·diag(τ_M))·vᵀ,   V = (u²·diag(τ_V))·(v²)ᵀ          (paper Eq. 8)

The lightweight second moment is the paper's key memory trick; this kernel is
the matching *bandwidth* trick: the naive lowering materializes both M and V
(two parameter-sized HBM buffers) before the elementwise update — 5·mn·bytes
of traffic.  Fused, each W tile makes one HBM round-trip (2·mn·bytes) and M/V
tiles exist only in VMEM; both reconstructions are MXU matmuls on the same
resident u/v slices.

Restore-into-update (``tau_r`` + ``restore_scale``): the perturbation-chain
schedule (core.zo_step) folds Algorithm 1's final restore pass — W ←
W + ρ·recon(τ_q) for the last probe — into this same W round-trip.  The
restore delta is applied first, with a cast to the weight dtype and back to
f32, so the arithmetic (and therefore the trajectory) is bitwise identical
to the separate restore pass it replaces; the Adam update then reads the
restored tile.  ``decay`` (1 − lr·wd) applies to the update only, exactly as
in the unchained two-pass order of operations.

Tiling as in kernels/tezo_perturb.py: ``ops.tezo_tiles`` sizes the block
from a VMEM budget that counts this body's extra f32 tiles (M and V),
the grid is ``(cdiv(m, bm), cdiv(n, bn))`` with a partial last block on
dims the block does not divide, and W is updated in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fence
from repro.kernels.tezo_perturb import VMEM_BUDGET


def _adam_body(sc_ref, w_ref, u_ref, v_ref, tm_ref, tv_ref, o_ref, tr_ref,
               barrier=False):
    u = u_ref[...].astype(jnp.float32)       # [bm, r]
    v = v_ref[...].astype(jnp.float32)       # [bn, r]
    tm = tm_ref[...].astype(jnp.float32)     # [1, r]
    tv = tv_ref[...].astype(jnp.float32)     # [1, r]
    wf = w_ref[...].astype(jnp.float32)
    if tr_ref is not None:
        # fold the restore delta(s) — sc[3+i]·recon(τ_rᵢ) for each row of the
        # stacked [k, r] restore block — into this pass, each round-tripped
        # through the VMEM output tile with the same rounding the separate
        # restore passes had (bitwise).  In interpret mode each delta runs
        # in its own fence branch in tezo_perturb's exact (d·W + s·Z) form
        # (d laundered to 1 here) so the replay matches the perturb passes
        # it undoes bit for bit — see kernels/fence.py.  The sequential
        # chained step hands a single +ρ·τ_{q−1} row; the probe-parallel
        # step hands the full 3q-delta trajectory restore.
        trs = tr_ref[...].astype(jnp.float32)      # [k, r]
        for idx in range(trs.shape[0]):
            if barrier:
                zero = fence.data_zero(wf)
                one = 1.0 + zero
                rsc = sc_ref[3 + idx] + zero
                tau_s = trs[idx : idx + 1, :] + zero

                def rdelta(wf=wf, one=one, rsc=rsc, tau_s=tau_s):
                    zr = jax.lax.dot_general(
                        u * tau_s, v, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    return (one * wf + rsc * zr).astype(o_ref.dtype)

                val = fence.fenced(
                    zero, rdelta, lambda wf=wf: wf.astype(o_ref.dtype)
                )
            else:
                zr = jax.lax.dot_general(
                    u * trs[idx : idx + 1, :], v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                val = (wf + sc_ref[3 + idx] * zr).astype(o_ref.dtype)
            o_ref[...] = val
            wf = o_ref[...].astype(jnp.float32)

    def update(wf=wf, zero=None):
        # laundered hyperparameters under the fence: the chained and
        # unchained schedules (and the probe-parallel replay) must compile
        # this tail identically whatever surrounds the kernel
        launder = zero if zero is not None else jnp.float32(0)
        lr = sc_ref[0] + launder
        eps = sc_ref[1] + launder
        decay = sc_ref[2] + launder
        m = jax.lax.dot_general(
            u * (tm + launder), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        vv = jax.lax.dot_general(
            (u * u) * (tv + launder), v * v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        g = m * jax.lax.rsqrt(vv + eps)
        return (decay * wf - lr * g).astype(o_ref.dtype)

    if barrier:
        zero = fence.data_zero(wf)
        o_ref[...] = fence.fenced(
            zero, lambda wf=wf, zero=zero: update(wf, zero),
            lambda wf=wf: wf.astype(o_ref.dtype),
        )
    else:
        o_ref[...] = update()


def _adam_kernel(sc_ref, w_ref, u_ref, v_ref, tm_ref, tv_ref, o_ref, *, barrier):
    _adam_body(sc_ref, w_ref, u_ref, v_ref, tm_ref, tv_ref, o_ref, None,
               barrier=barrier)


def _adam_restore_kernel(
    sc_ref, w_ref, u_ref, v_ref, tm_ref, tv_ref, tr_ref, o_ref, *, barrier
):
    _adam_body(
        sc_ref, w_ref, u_ref, v_ref, tm_ref, tv_ref, o_ref, tr_ref,
        barrier=barrier,
    )


@functools.partial(jax.jit, static_argnames=("eps", "bm", "bn", "interpret"))
def tezo_adam_update(
    w: jax.Array,        # [m, n]
    u: jax.Array,        # [m, r]
    v: jax.Array,        # [n, r]
    tau_m: jax.Array,    # [r] f32
    tau_v: jax.Array,    # [r] f32, nonnegative
    lr: jax.Array | float,
    eps: float = 1e-5,
    decay: jax.Array | float = 1.0,   # 1 − lr·wd (decoupled decay), 1.0 = none
    tau_r: jax.Array | None = None,   # [r] (or stacked [k·r]/[k, r]) f32:
    #                                   restore-into-update τ chain
    restore_scale: jax.Array | float = 0.0,   # scalar, or [k] matching tau_r
    *,
    bm: int = 256,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    m, n = w.shape
    r = u.shape[-1]
    k_r = 1 if tau_r is None else tau_r.reshape((-1, r)).shape[0]
    rs = jnp.asarray(restore_scale, jnp.float32).reshape(-1)
    assert rs.shape[0] in (1, k_r), (rs.shape, k_r)
    if rs.shape[0] != k_r:
        rs = jnp.broadcast_to(rs, (k_r,))
    sc = jnp.concatenate([
        jnp.stack([
            jnp.asarray(lr, jnp.float32),
            jnp.asarray(eps, jnp.float32),
            jnp.asarray(decay, jnp.float32),
        ]),
        rs,
    ])
    tile = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        tile,
        pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
        pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
        pl.BlockSpec((1, r), lambda i, j: (0, 0)),
        pl.BlockSpec((1, r), lambda i, j: (0, 0)),
    ]
    operands = [sc, w, u, v, tau_m.reshape(1, r), tau_v.reshape(1, r)]
    kernel = functools.partial(_adam_kernel, barrier=interpret)
    if tau_r is not None:
        in_specs.append(pl.BlockSpec((k_r, r), lambda i, j: (0, 0)))
        operands.append(tau_r.reshape(k_r, r))
        kernel = functools.partial(_adam_restore_kernel, barrier=interpret)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(*operands)
