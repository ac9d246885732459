"""Compute-dispatch layer: the single authority that routes the WHOLE
step's compute — every ZO method's perturb/update leaf ops AND the forward
kernels (flash attention, Mamba selective scan) — to Pallas or XLA.

Every ZO method touches every parameter leaf on each of the step's
full-parameter passes — 2q+1 under the chained transition schedule of
``core.zo_step`` (first_perturb / flip / bridge / restore_into_update),
3q+1 on the unchained branch.  The naive XLA lowering materializes the
perturbation ``Z`` — a dense parameter-sized buffer — in HBM for each of
those touches; the fused kernels in ``repro.kernels`` keep Z (and any
reconstructed moments) tile-resident in VMEM so each weight leaf makes
exactly one HBM round-trip per pass, and the chain leaf ops
(``perturb_pair_leaf`` / ``noise_perturb_pair_leaf`` / the ``restore_*``
update operands) merge two logical passes into one such round-trip with
bitwise-identical arithmetic.  And
because ZO fine-tuning has no backward pass, the three forward passes those
perturbations feed are ~all of step walltime — so the forward compute
dispatches here too (see the forward-path section at the bottom:
:func:`attention_fwd` / :func:`selective_scan_fwd`, selected by the same
``kernel_mode`` threaded through ``ModelConfig``).  This module is the
single place that decides which lowering runs — for *all nine* methods in
``estimator.METHODS``:

  TeZO family   Z = Σ_s τ_s(u_s∘v_s)   → kernels.tezo_perturb / tezo_adam
  MeZO family   Z ~ N(0, I_d) dense    → kernels.zo_noise (on-chip counter
                PRNG; q-probe mean and the dense m/v moment updates fused)
  LOZO (+m)     Z = U·Vᵀ               → tezo tiling with τ ≡ 1
  SubZO         Z = U·Σ·Vᵀ             → zo_noise.subzo_perturb (Σ core)

Dispatch rules
--------------
* ``kernel_mode`` (a jit-static field on :class:`repro.core.ZOConfig`):

  - ``"auto"``   → ``"pallas"`` when the default JAX backend is TPU, else
    ``"xla"``.  (The Pallas kernels *can* run anywhere via interpret mode —
    that is the correctness/testing path, not a speed path, so CPU autos to
    XLA.)
  - ``"pallas"`` → force the fused kernels.  On non-TPU backends the kernel
    wrappers in ``repro.kernels.ops`` fall back to interpret mode
    automatically (or via ``ops.set_interpret(True)``), so this mode is
    usable in tests on CPU.
  - ``"xla"``    → force the dense-reconstruct jnp path everywhere.

* Per-leaf eligibility: leaves with two trailing matrix dims (≥ 8 each,
  the same predicate that assigns CPD factors — see ``cpd.is_lowrank_leaf``)
  can take a kernel path; the ops wrappers vmap over leading batch dims,
  pad rank to MXU lanes, and pad awkward (m, n) to the tile multiple.
  Biases / norm scales (ndim < 2 or a tiny dim) always use the jnp path
  regardless of ``kernel_mode`` — for every method, so the noise stream a
  leaf sees is a function of eligibility only, never of the method.

Numerics
--------
Factor-carried methods (TeZO/LOZO/SubZO): the factors come from HBM either
way, so the two lowerings agree tightly for f32 factors and within bf16
rounding of ρ·Z for bf16 factors (the kernels accumulate in f32; the dense
path rounds Z to the factor dtype) — ``tests/test_dispatch_parity.py`` locks
both end-to-end.

MeZO / dense-noise leaves: the kernel path generates z on-chip from a
counter-based Threefry stream (see ``kernels/zo_noise.py``) which is a
*different* N(0,1) stream than the XLA path's ``jax.random.normal`` — so
pallas-vs-xla parity here is *statistical* (moments/covariance) plus exact
three-pass self-consistency within each mode; it is NOT bitwise across
modes, and switching ``kernel_mode`` mid-run changes the noise realization
(never the distribution).  The kernel math itself is still locked bitwise
against the replayed-stream oracles in ``kernels/ref.py``.

Sharded dispatch
----------------
Under a device mesh the Pallas kernels cannot be partitioned by GSPMD (a
pallas_call has no SPMD rule — XLA would all-gather every sharded leaf to
run it replicated, exactly the parameter-sized HBM traffic the kernels
exist to remove).  When the step builder registers a mesh + per-leaf
``PartitionSpec`` table (:func:`shard_context`, threaded from
``zo_step.build_zo_train_step``), every kernel-path leaf op instead wraps
its ops call in ``jax.shard_map``: each device runs the fused
kernel on its **local** shard (local-shape pad-and-mask tiling), factor /
moment operands ride the specs that ``distributed.sharding.
mstate_shardings`` assigns (u inherits W's row sharding, v the column
sharding, τ-vectors replicated, dense moments the leaf's spec), and the
``zo_noise`` counter PRNG is seeded from **global** element coordinates —
the shard origin derived from the leaf's PartitionSpec and the device's
mesh position via ``lax.axis_index`` — so the noise stream is bit-identical
under any mesh layout (1×1, 8×1 FSDP, 2×4, TP-split columns, …) and the
three Algorithm-1 passes replay the same z on every device.  The XLA path
never wraps: dense jnp math partitions fine under GSPMD and its
``jax.random.normal`` draws are a function of the *global* leaf only.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.cpd import (
    CPDFactor,
    dense_noise,
    is_lowrank_leaf,
    reconstruct,
    reconstruct_squared,
)
from repro.core.quant import QuantLeaf, scaled_lut
from repro.kernels import fence, ops
from repro.kernels.zo_noise import MAX_ROWS

KERNEL_MODES = ("auto", "pallas", "xla")

# Every method routes its perturb/update through this layer now; kept as the
# explicit source of truth for launchers/benchmarks (and so a hypothetical
# kernel-less method can be registered without touching them).
KERNEL_METHODS = (
    "tezo", "tezo_m", "tezo_adam",
    "mezo", "mezo_m", "mezo_adam",
    "lozo", "lozo_m", "subzo",
)


def add_scaled(w: jax.Array, z: jax.Array, scale, decay=None) -> jax.Array:
    """decay·w + scale·z with everything formed in f32 before the cast back
    to the weight dtype (keeps ρ·z resolution under bf16 params).  The
    single source of truth for the XLA-path accumulation numerics — the
    Pallas kernels implement the same f32-accumulate-then-cast contract
    in-kernel.  ``decay`` is the decoupled weight-decay factor 1 − lr·wd on
    update touches (None ≡ 1.0 — skipped, an exact identity).

    Each call runs as its own fence branch (kernels/fence.py): the XLA-path
    delta is the exact accumulation the fused kernels replace, so its
    rounding must not depend on how the surrounding schedule groups deltas —
    the chained/unchained and probe-parallel/sequential contracts compare
    XLA trajectories too.
    """
    wf = w.astype(jnp.float32)
    zf = z.astype(jnp.float32)
    zero = fence.data_zero(wf)
    sc = jnp.asarray(scale, jnp.float32) + zero
    d = None if decay is None else jnp.asarray(decay, jnp.float32) + zero

    def compute(wf=wf, zf=zf, sc=sc, d=d, zero=zero):
        acc = wf if d is None else wf * d
        # + zero keeps the branch from FMA-contracting acc + sc·z: per-op
        # rounding, same as the eager arithmetic the tolerance-parity tests
        # compare the kernels against
        return (acc + (sc * zf + zero)).astype(w.dtype)

    return fence.fenced(zero, compute, lambda wf=wf: wf.astype(w.dtype))


def resolve_kernel_mode(mode: str) -> str:
    """Resolve a ZOConfig.kernel_mode to the concrete path ("pallas"|"xla").

    Raises early (at trace/build time, not step time) on unknown modes.
    """
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"unknown kernel_mode {mode!r}; expected one of {KERNEL_MODES}"
        )
    if mode != "auto":
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def kernel_execution(method: str, mode: str) -> tuple[str, bool]:
    """What actually executes for (method, kernel_mode): (path, interpret).

    ``path`` is the hot-path lowering the method will really take — "pallas"
    for every registered method when the mode resolves there (universal
    coverage), "xla" otherwise or for unregistered/FO methods.
    ``interpret`` marks a pallas path that runs via the interpreter (off-TPU
    or forced), i.e. a correctness run whose timings are not fused-kernel
    measurements.  The single definition launchers use to label records and
    warnings.
    """
    if method not in KERNEL_METHODS:
        return "xla", False
    resolved = resolve_kernel_mode(mode)
    if resolved == "pallas":
        return "pallas", bool(ops.is_interpret())
    return resolved, False


def use_pallas(cfg) -> bool:
    """True iff cfg routes eligible leaves through the fused Pallas kernels.

    Static at trace time: depends only on the (hashable) config and the
    backend, never on traced values — so it never adds a lax.cond.
    """
    return resolve_kernel_mode(cfg.kernel_mode) == "pallas"


# ---------------------------------------------------------------------------
# Shard-aware dispatch: mesh + per-leaf PartitionSpec context
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardCtx:
    """Trace-time sharding context for the kernel dispatch.

    ``specs`` maps leaf path (utils.tree keystr) → the leaf's PartitionSpec
    on ``mesh`` — the same table ``distributed.sharding.param_spec_table``
    derives from ``param_shardings``.  Registered by the step builder for
    the duration of one trace; leaves absent from the table are treated as
    replicated.
    """

    mesh: Mesh
    specs: Mapping[str, P]


_SHARD_CTX: Optional[ShardCtx] = None


@contextmanager
def shard_context(mesh: Optional[Mesh], specs: Optional[Mapping[str, P]]):
    """Register the mesh + leaf-spec table while tracing a sharded step.

    A ``None`` mesh is a no-op (single-device dispatch, the default), so
    builders can pass their mesh argument through unconditionally.
    """
    global _SHARD_CTX
    prev = _SHARD_CTX
    _SHARD_CTX = None if mesh is None else ShardCtx(mesh, dict(specs or {}))
    try:
        yield
    finally:
        _SHARD_CTX = prev


def _leaf_mesh_spec(path: str, ndim: int) -> tuple[Optional[Mesh], Optional[P]]:
    """(mesh, PartitionSpec padded to ndim) for a leaf, or (None, None)."""
    ctx = _SHARD_CTX
    if ctx is None:
        return None, None
    entries = tuple(ctx.specs.get(path) or ())
    return ctx.mesh, P(*(entries + (None,) * (ndim - len(entries))))


def _global_offsets(mesh: Mesh, spec: P, local_shape: tuple) -> jax.Array:
    """int32[ndim] global coordinates of this device's shard origin.

    Only meaningful inside shard_map (uses ``lax.axis_index``).  For a dim
    partitioned over a tuple of mesh axes the shard index follows GSPMD's
    row-major axis order, so offset = shard_index · local_dim recovers the
    element's global coordinate.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    offs = []
    for entry, dim in zip(tuple(spec), local_shape):
        if entry is None:
            offs.append(jnp.int32(0))
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        idx = jnp.int32(0)
        for ax in axes:
            idx = idx * sizes[ax] + jax.lax.axis_index(ax)
        offs.append(idx * dim)
    return jnp.stack(offs)


def _shard_call(fn, mesh: Mesh, in_specs, out_specs, *args):
    """shard_map(fn) with replication checking off (pallas_call has no
    replication rule; out-spec correctness is locked by the parity tests)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )(*args)


def _factor_specs(spec: P) -> tuple[P, P, P]:
    """(u, v, τ) PartitionSpecs mirroring a leaf's spec — the same rule as
    ``distributed.sharding.mstate_shardings``: u inherits the row sharding,
    v the column sharding, τ/rank vectors shard only over batch dims."""
    e = tuple(spec)
    batch = e[:-2]
    return (
        P(*batch, e[-2], None),
        P(*batch, e[-1], None),
        P(*batch, None),
    )


def _scalar_f32(x) -> jax.Array:
    return jnp.asarray(x, jnp.float32)


def _decay_f32(decay) -> jax.Array:
    """Concrete f32 decay operand for shard_map (None ≡ no decay ≡ 1.0 —
    shard_map needs an array, it cannot pass None through an in_spec)."""
    return jnp.asarray(1.0 if decay is None else decay, jnp.float32)


def kernel_eligible(factor: CPDFactor, w: jax.Array) -> bool:
    """Can this (factor, leaf) pair be lowered to the fused TeZO kernels?

    Any leaf that owns a factor qualifies: init_factors only decorates leaves
    with two trailing matrix dims (≥ 8 each), and the ops wrappers vmap over
    arbitrary leading batch dims and tile any (m, n).  Kept as an explicit
    predicate so future exotic leaves (e.g. ragged stacks) can opt out here
    without touching the estimator.
    """
    return factor is not None and w.ndim >= 2


def noise_kernel_eligible(w: jax.Array) -> bool:
    """Can this leaf's dense N(0,1) perturbation run on the noise kernels?

    Mirrors ``cpd.is_lowrank_leaf`` (two trailing matrix dims ≥ 8) plus the
    counter-layout row bound, so a leaf's eligibility — and therefore its
    noise stream — is identical across perturb and update and across every
    method that touches it.
    """
    return is_lowrank_leaf("", w) and w.shape[-2] < MAX_ROWS


# ---------------------------------------------------------------------------
# QuantLeaf leaf-op protocol
# ---------------------------------------------------------------------------
#
# A ``core.quant.QuantLeaf`` is an atomic pytree leaf that stands in for a
# dense ``[..., K, N]`` weight: packed b-bit codes + per-channel LUT
# (frozen), the CPD factors qu/qv (frozen), an r-vector ``acc`` (the
# accumulated temporal coefficient — the leaf's ONLY TeZO-family mutable
# state) and, for the MeZO family, a dense ``nacc`` delta buffer.  Every
# leaf op in this module accepts a QuantLeaf wherever it accepts a dense
# leaf and branches FIRST on the leaf kind, so the estimator closures are
# lowering- and representation-agnostic:
#
#   * TeZO-family ops (perturb/pair/chain/sgd_update/adam_update): the
#     delta ``scale·recon(τ)`` is closed in τ-space — ``acc += scale·τ``
#     via :func:`add_scaled` on the r-vector, one fenced f32 add per
#     logical delta.  ZERO weight-sized bytes move on any of the 2q+1
#     passes; the perturbed weight materializes only inside the forward's
#     dequant tile (:func:`quant_matmul_fwd`).  Because each chained delta
#     is the same fenced f32 add the unchained schedule performs, the
#     chained/unchained and probe-parallel contracts hold BITWISE on both
#     lowerings (there is no weight-dtype rounding at all on this path).
#     TeZO-Adam's second-moment normalization applies in τ-space
#     (upd = τ_m·rsqrt(τ_v + ε) — the factorwise preconditioner), a
#     documented deviation from the dense leaf's elementwise Eq.-8
#     reconstruction.
#   * MeZO-family noise ops: route to the same op on ``nacc`` (which has
#     the dense leaf's shape, dtype and tree path, so the global-coordinate
#     PRNG contract and the 2q+1 pass structure are preserved verbatim) and
#     rewrap.  This keeps the knob uniform; it is not a traffic win.
#   * Weight decay is rejected: decay scales the frozen packed base, which
#     neither τ-space nor nacc can express (``quant.validate_quant_config``
#     raises at build time; the guards here are the trace-time backstop).
#   * LOZO/SubZO never see QuantLeaves (``quant.QUANT_METHODS`` excludes
#     them at init).
#
# Sharding: the quant ops are plain jnp — GSPMD partitions them (acc is
# replicated-or-batch-sharded like any τ vector; nacc rides the dense
# leaf's spec) — so none of them consult the shard context.


def _quant_no_decay(decay) -> None:
    if decay is not None:
        raise ValueError(
            "weight decay is unsupported on quantized leaves (it scales the "
            "frozen packed base) — quant.validate_quant_config rejects this "
            "at build time"
        )


def _quant_nacc(w: QuantLeaf) -> jax.Array:
    if w.nacc is None:
        raise ValueError(
            "dense-noise op on a QuantLeaf without a noise buffer: "
            "quantize with with_nacc=True (MeZO-family methods) — "
            "see core.quant.quantize_for_config"
        )
    return w.nacc


def _quant_acc_chain(w: QuantLeaf, taus, scales, decay=None) -> QuantLeaf:
    """Apply k τ-space deltas ``acc += scaleᵢ·τᵢ`` in chain order — each via
    the same fenced f32 ``add_scaled`` the dense XLA path uses, so the
    grouping (chained vs unchained vs probe-parallel) never changes the
    rounding."""
    if decay is not None:
        raise ValueError(
            "weight decay is unsupported on quantized leaves (it scales the "
            "frozen packed base) — quant.validate_quant_config rejects this "
            "at build time"
        )
    acc = w.acc
    for tau, s in zip(taus, scales):
        acc = add_scaled(acc, tau, s)
    return w.replace(acc=acc)


# ---------------------------------------------------------------------------
# TeZO family leaf ops (factors from HBM, τ from the step key)
# ---------------------------------------------------------------------------


def _tezo_kernel_call(w, factor, tau, scale, decay, path: str) -> jax.Array:
    """Fused decay·W + scale·recon(τ) — shard_map'd over the mesh when a
    shard context is registered, plain ops call otherwise.  ``tau`` may be a
    stacked [..., k, r] transition chain with ``scale`` [k] (one W pass
    applying k deltas — see ops.tezo_perturb)."""
    mesh, spec = _leaf_mesh_spec(path, w.ndim)
    scale_a = jnp.asarray(scale, jnp.float32)
    if mesh is None:
        return ops.tezo_perturb(w, factor.u, factor.v, tau, scale_a, decay=decay)
    decay_a = _decay_f32(decay)
    u_s, v_s, t_s = _factor_specs(spec)

    def local_fn(w_l, u_l, v_l, t_l, s_l, d_l):
        return ops.tezo_perturb(w_l, u_l, v_l, t_l, s_l, decay=d_l)

    return _shard_call(
        local_fn, mesh, (spec, u_s, v_s, t_s, P(), P()), spec,
        w, factor.u, factor.v, tau, scale_a, decay_a,
    )


def perturb_leaf(
    w: jax.Array,
    factor: CPDFactor,
    tau: jax.Array,
    scale,
    *,
    use_kernel: bool,
    path: str = "",
) -> jax.Array:
    """W + scale·(u·diag(τ))·vᵀ for one low-rank leaf.

    Kernel path: fused HBM-resident add (Z never materialized); under a
    shard context each device touches only its local shard.  XLA path:
    dense reconstruct + f32 add (the pre-dispatch behaviour).  QuantLeaf:
    the delta closes in τ-space — ``acc += scale·τ``, zero weight bytes
    (see the QuantLeaf protocol section above).
    """
    if isinstance(w, QuantLeaf):
        return _quant_acc_chain(w, [tau], [scale])
    if use_kernel and kernel_eligible(factor, w):
        return _tezo_kernel_call(w, factor, tau, scale, None, path)
    return add_scaled(w, reconstruct(factor, tau), scale)


def _stack_taus(tau_a: jax.Array, tau_b: jax.Array) -> jax.Array:
    """[..., 2, r] chain from two per-probe τ vectors."""
    return jnp.stack([tau_a, tau_b], axis=-2)


def perturb_pair_leaf(
    w: jax.Array,
    factor: CPDFactor,
    tau_a: jax.Array,
    tau_b: jax.Array,
    scale_a,
    scale_b,
    *,
    use_kernel: bool,
    path: str = "",
) -> jax.Array:
    """Bridge transition: scale_a·recon(τ_a) then scale_b·recon(τ_b) — the
    restore of probe i and the perturb of probe i+1 — in ONE fused pass.

    Kernel path: the stacked-τ chain kernel rounds to the weight dtype
    between the deltas, so the result is bitwise identical to two
    ``perturb_leaf`` passes at half the HBM traffic.  XLA path: two dense
    adds (identical arithmetic to the unchained calls, for parity).
    QuantLeaf: two τ-space adds, bitwise identical to two ``perturb_leaf``
    calls by construction.
    """
    if isinstance(w, QuantLeaf):
        return _quant_acc_chain(w, [tau_a, tau_b], [scale_a, scale_b])
    if use_kernel and kernel_eligible(factor, w):
        scales = jnp.stack([_scalar_f32(scale_a), _scalar_f32(scale_b)])
        return _tezo_kernel_call(
            w, factor, _stack_taus(tau_a, tau_b), scales, None, path
        )
    w = add_scaled(w, reconstruct(factor, tau_a), scale_a)
    return add_scaled(w, reconstruct(factor, tau_b), scale_b)


def _chain_restores(restore_x, restore_scale):
    """Normalize a restore operand to (values list, scales list) — a
    list/tuple is a multi-delta restore chain (the probe-parallel
    trajectory restore), anything else a one-delta chain (the sequential
    restore-into-update)."""
    if isinstance(restore_x, (list, tuple)):
        return list(restore_x), list(restore_scale)
    return [restore_x], [restore_scale]


def perturb_chain_leaf(
    w: jax.Array,
    factor: CPDFactor,
    taus,
    scales,
    *,
    use_kernel: bool,
    path: str = "",
) -> jax.Array:
    """Arbitrary-k transition chain for one TeZO leaf: scalesᵢ·recon(τᵢ)
    applied in chain order — the probe-parallel catch-up (replay probes
    0..s−1's ±ρ triples, then open probe s) in ONE fused pass.

    Kernel path: the stacked-τ chain kernel rounds to the weight dtype
    between deltas, bitwise identical to k single ``perturb_leaf`` passes.
    XLA path: the same k dense adds.  QuantLeaf: the same k τ-space adds.
    """
    if isinstance(w, QuantLeaf):
        return _quant_acc_chain(w, list(taus), list(scales))
    if use_kernel and kernel_eligible(factor, w):
        scale_arr = jnp.stack([_scalar_f32(s) for s in scales])
        return _tezo_kernel_call(
            w, factor, jnp.stack(list(taus), axis=-2), scale_arr, None, path
        )
    for tau, s in zip(taus, scales):
        w = add_scaled(w, reconstruct(factor, tau), s)
    return w


def sgd_update_leaf(
    w: jax.Array,
    factor: CPDFactor,
    ktau: jax.Array,
    lr,
    *,
    use_kernel: bool,
    decay=None,
    path: str = "",
    restore_tau=None,
    restore_scale=0.0,
) -> jax.Array:
    """W ← decay·W − lr·reconstruct(ktau): the TeZO / TeZO-m descent step.

    ``ktau`` is the probe-averaged κτ (plain TeZO) or the τ-space momentum
    (TeZO-m) — either way the update is a scaled rank-r reconstruction, so
    the kernel path reuses the fused perturb kernel with scale = −lr;
    ``decay`` (1 − lr·wd, or None) folds decoupled weight decay into the
    same pass instead of a separate full-W round-trip.

    ``restore_tau`` + ``restore_scale`` (the chained restore-into-update)
    prepend the last probe's +ρ·recon(τ_q) restore to the same pass: the
    kernel path runs the two-delta τ chain (restore, then decayed update —
    bitwise identical to the separate restore pass), the XLA path composes
    the same two dense adds.  A list/tuple ``restore_tau`` (with matching
    scales) is a multi-delta restore chain — the probe-parallel trajectory
    restore — applied delta by delta before the update in the same pass.
    QuantLeaf: the restore chain and the −lr·κτ descent delta are all
    τ-space adds on ``acc``.
    """
    if isinstance(w, QuantLeaf):
        taus, scales = [], []
        if restore_tau is not None:
            taus, scales = _chain_restores(restore_tau, restore_scale)
        return _quant_acc_chain(
            w, taus + [ktau], scales + [-_scalar_f32(lr)], decay
        )
    if use_kernel and kernel_eligible(factor, w):
        if restore_tau is not None:
            if isinstance(restore_tau, (list, tuple)):
                scales = jnp.stack(
                    [_scalar_f32(s) for s in restore_scale]
                    + [-_scalar_f32(lr)]
                )
                taus = jnp.concatenate(
                    [jnp.stack(list(restore_tau), axis=-2),
                     ktau[..., None, :]],
                    axis=-2,
                )
            else:
                scales = jnp.stack(
                    [_scalar_f32(restore_scale), -_scalar_f32(lr)]
                )
                taus = _stack_taus(restore_tau, ktau)
            return _tezo_kernel_call(w, factor, taus, scales, decay, path)
        return _tezo_kernel_call(w, factor, ktau, -lr, decay, path)
    if restore_tau is not None:
        for rt, rs in zip(*_chain_restores(restore_tau, restore_scale)):
            w = add_scaled(w, reconstruct(factor, rt), rs)
    return add_scaled(w, reconstruct(factor, ktau), -lr, decay=decay)


def adam_update_leaf(
    w: jax.Array,
    factor: CPDFactor,
    tau_m: jax.Array,
    tau_v: jax.Array,
    lr,
    eps: float,
    *,
    use_kernel: bool,
    decay=None,
    path: str = "",
    restore_tau=None,
    restore_scale=0.0,
) -> jax.Array:
    """W ← decay·W − lr·M/√(V+ε) with M, V reconstructed from τ-space
    moments (Eq. 8).

    Kernel path: both reconstructions stay in VMEM (one HBM round-trip per W
    tile instead of materializing two parameter-sized moment buffers), and
    the decoupled weight decay rides the same pass.  ``restore_tau`` +
    ``restore_scale`` fold the chained +ρ·recon(τ_q) restore into the same
    pass (applied before the Adam math, with the replaced pass's rounding).

    QuantLeaf: the Adam normalization applies in τ-space — the restore
    chain adds on ``acc``, then ``acc += −lr·τ_m·rsqrt(τ_v + ε)`` (the
    factorwise preconditioner; a documented deviation from the dense
    leaf's elementwise Eq.-8 reconstruction — see the protocol section).
    """
    if isinstance(w, QuantLeaf):
        taus, scales = [], []
        if restore_tau is not None:
            taus, scales = _chain_restores(restore_tau, restore_scale)
        upd = tau_m.astype(jnp.float32) * jax.lax.rsqrt(
            tau_v.astype(jnp.float32) + eps
        )
        return _quant_acc_chain(
            w, taus + [upd], scales + [-_scalar_f32(lr)], decay
        )
    if use_kernel and kernel_eligible(factor, w):
        mesh, spec = _leaf_mesh_spec(path, w.ndim)
        lr_a = _scalar_f32(lr)
        if isinstance(restore_tau, (list, tuple)):
            # multi-delta restore chain (probe-parallel trajectory restore):
            # stack to [..., k, r] — the kernel applies the rows in order
            rs_a = jnp.stack([_scalar_f32(s) for s in restore_scale])
            restore_tau = jnp.stack(list(restore_tau), axis=-2)
        else:
            rs_a = _scalar_f32(restore_scale)
        if mesh is None:
            return ops.tezo_adam_update(
                w, factor.u, factor.v, tau_m, tau_v, lr_a, eps, decay=decay,
                tau_r=restore_tau, restore_scale=rs_a,
            )
        decay_a = _decay_f32(decay)
        u_s, v_s, t_s = _factor_specs(spec)
        if restore_tau is None:

            def local_fn(w_l, u_l, v_l, tm_l, tv_l, lr_l, d_l):
                return ops.tezo_adam_update(
                    w_l, u_l, v_l, tm_l, tv_l, lr_l, eps, decay=d_l
                )

            return _shard_call(
                local_fn, mesh, (spec, u_s, v_s, t_s, t_s, P(), P()), spec,
                w, factor.u, factor.v, tau_m, tau_v, lr_a, decay_a,
            )

        def local_fn(w_l, u_l, v_l, tm_l, tv_l, tr_l, lr_l, d_l, rs_l):
            return ops.tezo_adam_update(
                w_l, u_l, v_l, tm_l, tv_l, lr_l, eps, decay=d_l,
                tau_r=tr_l, restore_scale=rs_l,
            )

        return _shard_call(
            local_fn, mesh,
            (spec, u_s, v_s, t_s, t_s, t_s, P(), P(), P()), spec,
            w, factor.u, factor.v, tau_m, tau_v, restore_tau,
            lr_a, decay_a, rs_a,
        )
    if restore_tau is not None:
        for rt, rs in zip(*_chain_restores(restore_tau, restore_scale)):
            w = add_scaled(w, reconstruct(factor, rt), rs)
    m_full = reconstruct(factor, tau_m).astype(jnp.float32)
    v_full = reconstruct_squared(factor, tau_v).astype(jnp.float32)
    return add_scaled(w, m_full * jax.lax.rsqrt(v_full + eps), -lr, decay=decay)


# ---------------------------------------------------------------------------
# Dense-noise leaf ops (MeZO family + every method's dense-fallback leaves)
# ---------------------------------------------------------------------------


def _noise_probe_mean(w, key_t, path: str, kappas) -> jax.Array:
    """mean_i κ_i·z_i for one leaf on the XLA path, regenerating z per probe.

    The z draws round to the leaf dtype first (jax.random.normal semantics
    of ``cpd.dense_noise``), matching the perturb pass exactly.
    """
    q = kappas.shape[0]
    zs = [
        dense_noise(w, key_t, path, i).astype(jnp.float32) for i in range(q)
    ]
    return fence.kappa_fold(kappas, zs)


def _decayed(w: jax.Array, decay) -> jax.Array:
    """f32 view of w with the optional decoupled decay factor applied."""
    wf = w.astype(jnp.float32)
    return wf if decay is None else wf * decay


def noise_perturb_leaf(
    w: jax.Array, key_t, path: str, probe: int, scale, *, use_kernel: bool
) -> jax.Array:
    """W + scale·z, z ~ N(0, I) — MeZO semantics for one leaf.

    Kernel path: z generated on-chip per tile (counter PRNG), one HBM
    round-trip; under a shard context the per-tile counters carry *global*
    element coordinates, so every mesh layout draws the same z.  XLA path:
    ``jax.random.normal`` dense buffer + f32 add.  The two streams differ
    (statistical parity only) but each is a pure function of (key_t, path,
    probe, global coords), so all three Algorithm-1 passes and the update
    replay the same z within a mode.  QuantLeaf: the op applies to the
    leaf's dense ``nacc`` delta buffer (same shape/dtype/path as the dense
    leaf it replaced — identical noise streams and pass structure).
    """
    if isinstance(w, QuantLeaf):
        return w.replace(nacc=noise_perturb_leaf(
            _quant_nacc(w), key_t, path, probe, scale, use_kernel=use_kernel
        ))
    if use_kernel and noise_kernel_eligible(w):
        seed = ops.leaf_seed(key_t, path)
        mesh, spec = _leaf_mesh_spec(path, w.ndim)
        scale_a = _scalar_f32(scale)
        if mesh is None:
            return ops.noise_perturb(w, seed, scale_a, probe=probe)

        def local_fn(w_l, seed_l, s_l):
            offs = _global_offsets(mesh, spec, w_l.shape)
            return ops.noise_perturb(w_l, seed_l, s_l, probe=probe, offsets=offs)

        return _shard_call(
            local_fn, mesh, (spec, P(), P()), spec, w, seed, scale_a
        )
    return add_scaled(w, dense_noise(w, key_t, path, probe), scale)


def noise_perturb_pair_leaf(
    w: jax.Array, key_t, path: str, probe_a: int, scale_a, probe_b: int,
    scale_b, *, use_kernel: bool,
) -> jax.Array:
    """Chained bridge for one dense-noise leaf: W + scale_a·z_a + scale_b·z_b
    (restore probe a, perturb probe b) in one pass.

    Kernel path: the dual-draw kernel generates both probes' z in the same
    tile visit — bitwise identical to two ``noise_perturb_leaf`` passes
    (identical per-probe counter streams), half the HBM traffic; global-
    coordinate seeding keeps it mesh-layout-invariant like the single-draw
    op.  XLA path: two dense ``jax.random`` adds, identical arithmetic to
    the unchained calls.  QuantLeaf: applies to ``nacc``.
    """
    if isinstance(w, QuantLeaf):
        return w.replace(nacc=noise_perturb_pair_leaf(
            _quant_nacc(w), key_t, path, probe_a, scale_a, probe_b, scale_b,
            use_kernel=use_kernel,
        ))
    if use_kernel and noise_kernel_eligible(w):
        seed = ops.leaf_seed(key_t, path)
        mesh, spec = _leaf_mesh_spec(path, w.ndim)
        sa, sb = _scalar_f32(scale_a), _scalar_f32(scale_b)
        if mesh is None:
            return ops.noise_perturb_pair(
                w, seed, sa, sb, probe_a=probe_a, probe_b=probe_b
            )

        def local_fn(w_l, seed_l, sa_l, sb_l):
            offs = _global_offsets(mesh, spec, w_l.shape)
            return ops.noise_perturb_pair(
                w_l, seed_l, sa_l, sb_l, probe_a=probe_a, probe_b=probe_b,
                offsets=offs,
            )

        return _shard_call(
            local_fn, mesh, (spec, P(), P(), P()), spec, w, seed, sa, sb
        )
    w = add_scaled(w, dense_noise(w, key_t, path, probe_a), scale_a)
    return add_scaled(w, dense_noise(w, key_t, path, probe_b), scale_b)


def noise_perturb_chain_leaf(
    w: jax.Array, key_t, path: str, probes, scales, *, use_kernel: bool
) -> jax.Array:
    """Arbitrary-k transition chain for one dense-noise leaf: scalesᵢ·z_pᵢ
    applied in chain order — the probe-parallel catch-up chain.  Kernel
    path: the multi-draw kernel generates every probe's z in the same tile
    visit (one W round-trip), bitwise identical to k ``noise_perturb_leaf``
    passes; global-coordinate seeding keeps it mesh-layout-invariant.  XLA
    path: the same k dense adds.  QuantLeaf: applies to ``nacc``."""
    if isinstance(w, QuantLeaf):
        return w.replace(nacc=noise_perturb_chain_leaf(
            _quant_nacc(w), key_t, path, probes, scales, use_kernel=use_kernel
        ))
    probes_t = tuple(probes)
    if use_kernel and noise_kernel_eligible(w):
        seed = ops.leaf_seed(key_t, path)
        mesh, spec = _leaf_mesh_spec(path, w.ndim)
        scale_arr = jnp.stack([_scalar_f32(s) for s in scales])
        if mesh is None:
            return ops.noise_perturb(w, seed, scale_arr, probe=probes_t)

        def local_fn(w_l, seed_l, s_l):
            offs = _global_offsets(mesh, spec, w_l.shape)
            return ops.noise_perturb(
                w_l, seed_l, s_l, probe=probes_t, offsets=offs
            )

        return _shard_call(
            local_fn, mesh, (spec, P(), P()), spec, w, seed, scale_arr
        )
    for p, s in zip(probes_t, scales):
        w = add_scaled(w, dense_noise(w, key_t, path, p), s)
    return w


def _noise_restored(w, key_t, path: str, restore_probe, restore_scale):
    """XLA-path restore-into-update prologue: the +ρ·z add(s) of the
    restore probe (or, for a tuple, the whole restore chain in order),
    identical to the separate restore pass(es) replaced."""
    if restore_probe is None:
        return w
    for p, s in zip(*_chain_restores(restore_probe, restore_scale)):
        w = add_scaled(w, dense_noise(w, key_t, path, p), s)
    return w


def _restore_statics(restore_probe, restore_scale):
    """(jit-static probe operand, f32 scale operand) for the fused noise
    updates: a list/tuple restore chain normalizes to (tuple, [k] array),
    a single restore to (int, scalar) — the kernels index hyp[5+i] per
    chain delta."""
    if isinstance(restore_probe, (list, tuple)):
        return tuple(restore_probe), jnp.stack(
            [_scalar_f32(s) for s in restore_scale]
        )
    return restore_probe, _scalar_f32(restore_scale)


def noise_sgd_update_leaf(
    w: jax.Array, key_t, path: str, kappas, lr, *, use_kernel: bool,
    decay=None, restore_probe=None, restore_scale=0.0,
) -> jax.Array:
    """W ← decay·W − lr·(mean_i κ_i z_i): the MeZO descent step for one
    leaf, probe mean and weight decay fused in-kernel on the pallas path.
    ``restore_probe`` folds the chained +restore_scale·z restore into the
    same pass (one extra on-chip draw; bitwise identical to the separate
    restore on both lowerings).  QuantLeaf: applies to ``nacc`` (decay is
    rejected upstream — it would scale the frozen packed base)."""
    if isinstance(w, QuantLeaf):
        _quant_no_decay(decay)
        return w.replace(nacc=noise_sgd_update_leaf(
            _quant_nacc(w), key_t, path, kappas, lr, use_kernel=use_kernel,
            restore_probe=restore_probe, restore_scale=restore_scale,
        ))
    if use_kernel and noise_kernel_eligible(w):
        seed = ops.leaf_seed(key_t, path)
        mesh, spec = _leaf_mesh_spec(path, w.ndim)
        lr_a = _scalar_f32(lr)
        restore_probe, rs_a = _restore_statics(restore_probe, restore_scale)
        if mesh is None:
            return ops.noise_update_sgd(
                w, seed, kappas, lr_a, decay=decay,
                restore_probe=restore_probe, restore_scale=rs_a,
            )
        decay_a = _decay_f32(decay)

        def local_fn(w_l, seed_l, kap_l, lr_l, d_l, rs_l):
            offs = _global_offsets(mesh, spec, w_l.shape)
            return ops.noise_update_sgd(
                w_l, seed_l, kap_l, lr_l, decay=d_l, offsets=offs,
                restore_probe=restore_probe, restore_scale=rs_l,
            )

        return _shard_call(
            local_fn, mesh, (spec, P(), P(), P(), P(), P()), spec,
            w, seed, kappas, lr_a, decay_a, rs_a,
        )
    w = _noise_restored(w, key_t, path, restore_probe, restore_scale)
    g = _noise_probe_mean(w, key_t, path, kappas)
    return (_decayed(w, decay) - lr * g).astype(w.dtype)


def noise_momentum_update_leaf(
    w: jax.Array, m_buf, key_t, path: str, kappas, lr, beta1, *,
    use_kernel: bool, decay=None, restore_probe=None, restore_scale=0.0,
):
    """Dense momentum step for one leaf: M ← β₁M + (1−β₁)g; W ← decay·W −
    lr·M.

    Returns (w', m').  Kernel path fuses the probe mean, the moment update,
    the weight decay, the weight update — and, when ``restore_probe`` is
    set, the chained restore — into one pass over (W, M).  QuantLeaf:
    applies to ``nacc`` (the f32 moment buffer is dense either way)."""
    if isinstance(w, QuantLeaf):
        _quant_no_decay(decay)
        nacc, m_new = noise_momentum_update_leaf(
            _quant_nacc(w), m_buf, key_t, path, kappas, lr, beta1,
            use_kernel=use_kernel, restore_probe=restore_probe,
            restore_scale=restore_scale,
        )
        return w.replace(nacc=nacc), m_new
    if use_kernel and noise_kernel_eligible(w):
        seed = ops.leaf_seed(key_t, path)
        mesh, spec = _leaf_mesh_spec(path, w.ndim)
        lr_a = _scalar_f32(lr)
        restore_probe, rs_a = _restore_statics(restore_probe, restore_scale)
        if mesh is None:
            return ops.noise_update_momentum(
                w, m_buf, seed, kappas, lr_a, beta1, decay=decay,
                restore_probe=restore_probe, restore_scale=rs_a,
            )
        decay_a = _decay_f32(decay)

        def local_fn(w_l, m_l, seed_l, kap_l, lr_l, d_l, rs_l):
            offs = _global_offsets(mesh, spec, w_l.shape)
            return ops.noise_update_momentum(
                w_l, m_l, seed_l, kap_l, lr_l, beta1, decay=d_l, offsets=offs,
                restore_probe=restore_probe, restore_scale=rs_l,
            )

        return _shard_call(
            local_fn, mesh, (spec, spec, P(), P(), P(), P(), P()),
            (spec, spec),
            w, m_buf, seed, kappas, lr_a, decay_a, rs_a,
        )
    w = _noise_restored(w, key_t, path, restore_probe, restore_scale)
    g = _noise_probe_mean(w, key_t, path, kappas)
    m_new = beta1 * m_buf + (1.0 - beta1) * g
    return (_decayed(w, decay) - lr * m_new).astype(w.dtype), m_new


def noise_adam_update_leaf(
    w: jax.Array, m_buf, v_buf, key_t, path: str, kappas, lr,
    beta1, beta2, eps, *, use_kernel: bool, decay=None,
    restore_probe=None, restore_scale=0.0,
):
    """Dense Adam step for one leaf; returns (w', m', v').  Kernel path
    makes one HBM round-trip per buffer instead of materializing g; the
    chained restore rides the same pass when ``restore_probe`` is set.
    QuantLeaf: applies to ``nacc``."""
    if isinstance(w, QuantLeaf):
        _quant_no_decay(decay)
        nacc, m_new, v_new = noise_adam_update_leaf(
            _quant_nacc(w), m_buf, v_buf, key_t, path, kappas, lr,
            beta1, beta2, eps, use_kernel=use_kernel,
            restore_probe=restore_probe, restore_scale=restore_scale,
        )
        return w.replace(nacc=nacc), m_new, v_new
    if use_kernel and noise_kernel_eligible(w):
        seed = ops.leaf_seed(key_t, path)
        mesh, spec = _leaf_mesh_spec(path, w.ndim)
        lr_a = _scalar_f32(lr)
        restore_probe, rs_a = _restore_statics(restore_probe, restore_scale)
        if mesh is None:
            return ops.noise_update_adam(
                w, m_buf, v_buf, seed, kappas, lr_a, beta1, beta2, eps,
                decay=decay, restore_probe=restore_probe, restore_scale=rs_a,
            )
        decay_a = _decay_f32(decay)

        def local_fn(w_l, m_l, v_l, seed_l, kap_l, lr_l, d_l, rs_l):
            offs = _global_offsets(mesh, spec, w_l.shape)
            return ops.noise_update_adam(
                w_l, m_l, v_l, seed_l, kap_l, lr_l, beta1, beta2, eps,
                decay=d_l, offsets=offs,
                restore_probe=restore_probe, restore_scale=rs_l,
            )

        return _shard_call(
            local_fn, mesh,
            (spec, spec, spec, P(), P(), P(), P(), P()), (spec, spec, spec),
            w, m_buf, v_buf, seed, kappas, lr_a, decay_a, rs_a,
        )
    w = _noise_restored(w, key_t, path, restore_probe, restore_scale)
    g = _noise_probe_mean(w, key_t, path, kappas)
    m_new = beta1 * m_buf + (1.0 - beta1) * g
    v_new = beta2 * v_buf + (1.0 - beta2) * g * g
    upd = m_new * jax.lax.rsqrt(v_new + eps)
    return (_decayed(w, decay) - lr * upd).astype(w.dtype), m_new, v_new


# ---------------------------------------------------------------------------
# LOZO / SubZO leaf ops (factors from HBM, like TeZO — parity is bitwise-ish)
# ---------------------------------------------------------------------------


def lozo_perturb_leaf(
    w: jax.Array, u, v, scale, *, use_kernel: bool, decay=None, path: str = ""
) -> jax.Array:
    """W + scale·U·Vᵀ (LOZO).  Kernel path reuses the tezo tiling (τ ≡ 1);
    under a shard context U rides the leaf's row sharding and V the column
    sharding, same as the stored CPD factors."""
    if use_kernel and w.ndim >= 2:
        mesh, spec = _leaf_mesh_spec(path, w.ndim)
        scale_a = _scalar_f32(scale)
        if mesh is None:
            return ops.lozo_perturb(w, u, v, scale_a, decay=decay)
        decay_a = _decay_f32(decay)
        u_s, v_s, _ = _factor_specs(spec)

        def local_fn(w_l, u_l, v_l, s_l, d_l):
            return ops.lozo_perturb(w_l, u_l, v_l, s_l, decay=d_l)

        return _shard_call(
            local_fn, mesh, (spec, u_s, v_s, P(), P()), spec,
            w, u, v, scale_a, decay_a,
        )
    return add_scaled(w, jnp.einsum("...mr,...nr->...mn", u, v), scale, decay=decay)


def _lozo_chain_call(w, u, v_a, v_b, scale_a, scale_b, decay, path: str):
    """Two LOZO deltas (shared lazy U, two fresh V factors) in one fused
    pass — shard_map'd like the single-delta op; the widened 2r factors ride
    the same row/column specs."""
    mesh, spec = _leaf_mesh_spec(path, w.ndim)
    sa, sb = _scalar_f32(scale_a), _scalar_f32(scale_b)
    if mesh is None:
        return ops.lozo_chain(w, u, v_a, v_b, sa, sb, decay=decay)
    decay_a = _decay_f32(decay)
    u_s, v_s, _ = _factor_specs(spec)

    def local_fn(w_l, u_l, va_l, vb_l, sa_l, sb_l, d_l):
        return ops.lozo_chain(w_l, u_l, va_l, vb_l, sa_l, sb_l, decay=d_l)

    return _shard_call(
        local_fn, mesh, (spec, u_s, v_s, v_s, P(), P(), P()), spec,
        w, u, v_a, v_b, sa, sb, decay_a,
    )


def _lozo_chain_k_call(w, u, vs, scales, decay, path: str):
    """k LOZO deltas (shared lazy U, k fresh V factors) in one fused pass —
    the arbitrary-k twin of ``_lozo_chain_call`` for the probe-parallel
    catch-up and trajectory-restore chains."""
    mesh, spec = _leaf_mesh_spec(path, w.ndim)
    scale_ops = [_scalar_f32(s) for s in scales]
    if mesh is None:
        return ops.lozo_chain_k(w, u, list(vs), scale_ops, decay=decay)
    decay_a = _decay_f32(decay)
    u_s, v_s, _ = _factor_specs(spec)
    k = len(vs)

    def local_fn(w_l, u_l, *rest):
        return ops.lozo_chain_k(
            w_l, u_l, list(rest[:k]), list(rest[k : 2 * k]), decay=rest[-1]
        )

    return _shard_call(
        local_fn, mesh,
        (spec, u_s) + (v_s,) * k + (P(),) * (k + 1), spec,
        w, u, *vs, *scale_ops, decay_a,
    )


def lozo_perturb_chain_leaf(
    w: jax.Array, u, vs, scales, *, use_kernel: bool, path: str = ""
) -> jax.Array:
    """Arbitrary-k transition chain for one LOZO leaf: scalesᵢ·U·Vᵢᵀ in
    chain order (the probe-parallel catch-up), one fused pass on the kernel
    path — bitwise identical to k ``lozo_perturb_leaf`` passes."""
    if use_kernel and w.ndim >= 2:
        return _lozo_chain_k_call(w, u, vs, scales, None, path)
    for v_i, s in zip(vs, scales):
        w = add_scaled(w, jnp.einsum("...mr,...nr->...mn", u, v_i), s)
    return w


def lozo_perturb_pair_leaf(
    w: jax.Array, u, v_a, v_b, scale_a, scale_b, *, use_kernel: bool,
    path: str = "",
) -> jax.Array:
    """Bridge transition for LOZO: scale_a·U·V_aᵀ + scale_b·U·V_bᵀ (restore
    probe a, perturb probe b — U is window-lazy, shared) in one pass;
    bitwise identical to two ``lozo_perturb_leaf`` passes."""
    if use_kernel and w.ndim >= 2:
        return _lozo_chain_call(w, u, v_a, v_b, scale_a, scale_b, None, path)
    w = add_scaled(w, jnp.einsum("...mr,...nr->...mn", u, v_a), scale_a)
    return add_scaled(w, jnp.einsum("...mr,...nr->...mn", u, v_b), scale_b)


def lozo_update_leaf(
    w: jax.Array, u, kv, lr, *, use_kernel: bool, decay=None, path: str = "",
    restore_v=None, restore_scale=0.0,
) -> jax.Array:
    """W ← decay·W − lr·U·(kv)ᵀ where ``kv`` is the probe-averaged κ·V (or
    the LOZO-m factored momentum) — the whole gradient signal lives in the
    [n, r] factor, so the update is one fused rank-r pass.

    ``restore_v`` + ``restore_scale`` fold the chained +ρ·U·V_qᵀ restore of
    the last probe into the same pass (the V-factor twin of the τ-chain);
    a list/tuple ``restore_v`` is the multi-delta probe-parallel trajectory
    restore, applied in order before the update delta."""
    if restore_v is not None:
        if use_kernel and w.ndim >= 2:
            if isinstance(restore_v, (list, tuple)):
                return _lozo_chain_k_call(
                    w, u, list(restore_v) + [kv],
                    list(restore_scale) + [-_scalar_f32(lr)], decay, path,
                )
            return _lozo_chain_call(
                w, u, restore_v, kv, restore_scale, -lr, decay, path
            )
        for rv, rs in zip(*_chain_restores(restore_v, restore_scale)):
            w = add_scaled(
                w, jnp.einsum("...mr,...nr->...mn", u, rv), rs
            )
        return add_scaled(
            w, jnp.einsum("...mr,...nr->...mn", u, kv), -lr, decay=decay
        )
    return lozo_perturb_leaf(
        w, u, kv, -lr, use_kernel=use_kernel, decay=decay, path=path
    )


def subzo_perturb_leaf(
    w: jax.Array, u, v, sigma, scale, *, use_kernel: bool, decay=None,
    path: str = "",
) -> jax.Array:
    """W + scale·U·Σ·Vᵀ (SubZO).  The tiny [r, r] Σ core is replicated
    across the mesh; U/V ride the leaf's row/column sharding."""
    if use_kernel and w.ndim >= 2:
        mesh, spec = _leaf_mesh_spec(path, w.ndim)
        scale_a = _scalar_f32(scale)
        if mesh is None:
            return ops.subzo_perturb(w, u, v, sigma, scale_a, decay=decay)
        decay_a = _decay_f32(decay)
        u_s, v_s, _ = _factor_specs(spec)
        sig_s = P(*tuple(spec)[:-2], None, None)

        def local_fn(w_l, u_l, v_l, sig_l, s_l, d_l):
            return ops.subzo_perturb(w_l, u_l, v_l, sig_l, s_l, decay=d_l)

        return _shard_call(
            local_fn, mesh, (spec, u_s, v_s, sig_s, P(), P()), spec,
            w, u, v, sigma, scale_a, decay_a,
        )
    return add_scaled(
        w, jnp.einsum("...mr,...rk,...nk->...mn", u, sigma, v), scale, decay=decay
    )


def _stack_sigmas(sig_a, sig_b):
    """[..., 2, r, r] chain from two Σ cores."""
    return jnp.stack([sig_a, sig_b], axis=-3)


def subzo_perturb_pair_leaf(
    w: jax.Array, u, v, sig_a, sig_b, scale_a, scale_b, *, use_kernel: bool,
    path: str = "",
) -> jax.Array:
    """Bridge transition for SubZO: scale_a·U·Σ_a·Vᵀ + scale_b·U·Σ_b·Vᵀ
    (restore probe a, perturb probe b — U, V window-lazy, shared) in one
    pass; bitwise identical to two ``subzo_perturb_leaf`` passes."""
    if use_kernel and w.ndim >= 2:
        scales = jnp.stack([_scalar_f32(scale_a), _scalar_f32(scale_b)])
        return subzo_perturb_leaf(
            w, u, v, _stack_sigmas(sig_a, sig_b), scales,
            use_kernel=True, path=path,
        )
    w = add_scaled(
        w, jnp.einsum("...mr,...rk,...nk->...mn", u, sig_a, v), scale_a
    )
    return add_scaled(
        w, jnp.einsum("...mr,...rk,...nk->...mn", u, sig_b, v), scale_b
    )


def subzo_perturb_chain_leaf(
    w: jax.Array, u, v, sigmas, scales, *, use_kernel: bool, path: str = ""
) -> jax.Array:
    """Arbitrary-k transition chain for one SubZO leaf: scalesᵢ·U·Σᵢ·Vᵀ in
    chain order (U, V window-lazy, shared — the probe-parallel catch-up),
    one fused pass on the kernel path; bitwise identical to k
    ``subzo_perturb_leaf`` passes."""
    if use_kernel and w.ndim >= 2:
        scale_arr = jnp.stack([_scalar_f32(s) for s in scales])
        return subzo_perturb_leaf(
            w, u, v, jnp.stack(list(sigmas), axis=-3), scale_arr,
            use_kernel=True, path=path,
        )
    for sig, s in zip(sigmas, scales):
        w = add_scaled(
            w, jnp.einsum("...mr,...rk,...nk->...mn", u, sig, v), s
        )
    return w


def subzo_update_leaf(
    w: jax.Array, u, v, sbar, lr, *, use_kernel: bool, decay=None,
    path: str = "", restore_sigma=None, restore_scale=0.0,
) -> jax.Array:
    """W ← decay·W − lr·U·(mean_i κ_i Σ_i)·Vᵀ: the probe mean collapses onto
    the tiny [r, r] core, then one fused rank-r pass applies it.

    ``restore_sigma`` + ``restore_scale`` fold the chained +ρ·U·Σ_q·Vᵀ
    restore into the same pass (a two-core Σ chain; decay hits the update
    delta only); a list/tuple ``restore_sigma`` is the multi-delta
    probe-parallel trajectory restore, applied in order."""
    if restore_sigma is not None:
        if use_kernel and w.ndim >= 2:
            if isinstance(restore_sigma, (list, tuple)):
                scales = jnp.stack(
                    [_scalar_f32(s) for s in restore_scale]
                    + [-_scalar_f32(lr)]
                )
                sig_chain = jnp.stack(
                    list(restore_sigma) + [sbar], axis=-3
                )
            else:
                scales = jnp.stack(
                    [_scalar_f32(restore_scale), -_scalar_f32(lr)]
                )
                sig_chain = _stack_sigmas(restore_sigma, sbar)
            return subzo_perturb_leaf(
                w, u, v, sig_chain, scales,
                use_kernel=True, decay=decay, path=path,
            )
        for rs_sig, rs_sc in zip(*_chain_restores(restore_sigma, restore_scale)):
            w = add_scaled(
                w, jnp.einsum("...mr,...rk,...nk->...mn", u, rs_sig, v),
                rs_sc,
            )
        return add_scaled(
            w, jnp.einsum("...mr,...rk,...nk->...mn", u, sbar, v), -lr,
            decay=decay,
        )
    return subzo_perturb_leaf(
        w, u, v, sbar, -lr, use_kernel=use_kernel, decay=decay, path=path
    )


# ---------------------------------------------------------------------------
# Forward-path dispatch: flash attention + selective scan
#
# ZO fine-tuning has no backward pass, so Algorithm 1's three forward passes
# dominate step walltime — the forward compute kernels are first-class
# dispatch citizens exactly like the ZO leaf ops above.  The knob is the
# same jit-static ``kernel_mode`` (``ModelConfig.kernel_mode``, threaded
# from ``ZOConfig.kernel_mode`` by the launchers so one switch rules the
# whole step); ``ModelConfig.attention_impl`` is retired (a deprecation
# shim maps it onto kernel_mode).
#
# Execution matrix for a resolved "pallas" forward:
#   * TPU                       → the Mosaic kernels (kernels/flash_attention,
#                                 kernels/selective_scan), pad-and-mask via
#                                 the ops wrappers.
#   * CPU, interpret FORCED     → the same kernels through the Pallas
#     (ops.set_interpret(True))   interpreter — the cross-lowering parity
#                                 path the forward tests use.
#   * CPU, auto-detected        → the online-softmax / sequential-scan XLA
#                                 twins inside a PALLAS_FLASH_REGION named
#                                 scope, so the dry-run's HLO analyzer costs
#                                 the region with the kernel's HBM model
#                                 (launch/hlo_analysis.py) instead of paying
#                                 interpreter emulation in the hot forward.
#
# Sharded forward: a pallas_call has no GSPMD partitioning rule, so under a
# registered :func:`shard_context` the kernel path wraps in shard_map over
# the model's BATCH axes and — when the head/channel dim divides the
# "model" axis — the tensor-parallel HEAD/CHANNEL shard too (attention is
# per-head and the scan per-channel, so neither needs cross-device math);
# remaining operands are replicated.  Consistent with how the ZO leaf ops
# shard.  The XLA paths never wrap (GSPMD partitions them).
# ---------------------------------------------------------------------------


def forward_execution(mode: str) -> tuple[str, bool]:
    """What the forward compute executes for a kernel_mode: (path, kernel).

    ``path`` is "pallas" | "xla"; ``kernel`` is True when the real Pallas
    kernel runs (Mosaic on TPU, or the interpreter when a test forced it) —
    False with path "pallas" means the marker-region XLA twin runs (the
    off-TPU production/dry-run lowering).  Static at trace time.
    """
    resolved = resolve_kernel_mode(mode)
    if resolved != "pallas":
        return "xla", False
    return "pallas", jax.default_backend() == "tpu" or ops.interpret_forced()


def _forward_mesh(batch_axes, batch_dim: int) -> tuple[Optional[Mesh], tuple]:
    """(mesh, batch axes present on it) when a shard context is registered
    and the leading batch dim divides their product (shard_map needs even
    shards; an indivisible batch falls back to the unwrapped kernel)."""
    ctx = _SHARD_CTX
    if ctx is None:
        return None, ()
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    ba = tuple(a for a in batch_axes if a in sizes)
    prod = 1
    for a in ba:
        prod *= sizes[a]
    if not ba or batch_dim % prod != 0:
        return None, ()
    return ctx.mesh, ba


def _forward_model_axis(mesh: Mesh, *dims: int) -> Optional[str]:
    """The tensor-parallel ("model") mesh axis for a forward kernel, when
    every dim in ``dims`` divides its size — attention heads and scan
    channels are shard-independent, so the kernel runs on its LOCAL head/
    channel shard instead of all-gathering the model axis and computing
    every head redundantly on each of its devices.  For GQA the KV-head
    divisibility requirement also keeps each local H chunk aligned to whole
    KV groups, so the in-kernel h → h//G mapping stays correct per shard."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    size = sizes.get("model", 1)
    if size > 1 and all(d % size == 0 for d in dims):
        return "model"
    return None


def attention_fwd(
    q: jax.Array,        # [B, S, H, dh]
    k: jax.Array,        # [B, T, KV, dh]
    v: jax.Array,        # [B, T, KV, dh]
    *,
    window: int = 0,
    q_offset=0,
    mode: str = "auto",
    batch_axes: tuple = (),
    chunk_q: int = 1024,
    chunk_k: int = 1024,
    chunked_min_seq: int = 8192,
) -> jax.Array:
    """Causal (GQA / sliding-window) prefill attention for one block.

    The single authority for which attention lowering runs — models call
    this via ``layers.attention`` and never branch on an impl knob
    themselves.  XLA path keeps the pre-dispatch behaviour: materialized
    scores under ``chunked_min_seq``, the online-softmax chunked twin above.
    """
    from repro.models import layers  # lazy: layers imports this module

    path, kernel = forward_execution(mode)
    if path == "pallas" and kernel:
        mesh, ba = _forward_mesh(batch_axes, q.shape[0])
        if mesh is None:
            return ops.flash_attention(q, k, v, window=window, q_offset=q_offset)
        m_ax = _forward_model_axis(mesh, q.shape[2], k.shape[2])
        spec = P(ba, None, m_ax, None)

        def local_fn(q_l, k_l, v_l):
            return ops.flash_attention(
                q_l, k_l, v_l, window=window, q_offset=q_offset
            )

        return _shard_call(local_fn, mesh, (spec, spec, spec), spec, q, k, v)
    if path == "pallas":
        with jax.named_scope("PALLAS_FLASH_REGION"):
            return layers.chunked_attention(
                q, k, v, window=window, q_offset=q_offset,
                chunk_q=chunk_q, chunk_k=chunk_k,
            )
    if q.shape[1] >= chunked_min_seq:
        return layers.chunked_attention(
            q, k, v, window=window, q_offset=q_offset,
            chunk_q=chunk_q, chunk_k=chunk_k,
        )
    return layers.full_attention(q, k, v, window=window, q_offset=q_offset)


def decode_attention_fwd(
    q: jax.Array,             # [S, H, dh] one query token per decode slot
    k_pages: jax.Array,       # [n_pages, page_size, KV, dh] shared page pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, pages_per_slot] int32 physical page ids
    lengths: jax.Array,       # [S] int32 valid kv length per slot
    *,
    mode: str = "auto",
) -> jax.Array:
    """Paged (block-table) KV-cache decode attention for one step.

    The serving-engine sibling of :func:`attention_fwd`: models call this
    via ``layers.paged_decode_attention`` and never branch on an impl knob
    themselves.  Pallas path runs the block-table kernel
    (kernels/decode_attention — Mosaic on TPU, the interpreter when a test
    forced it); off-TPU auto-detection takes the gather-then-dense XLA twin
    inside the PALLAS_FLASH_REGION marker, matching the prefill kernel's
    costing convention.  No shard_map wrap: the decode batch dim is the
    engine's slot axis, not a mesh data axis — single-host serving runs
    unsharded (multi-host serving is the ROADMAP follow-on).
    """
    from repro.models import layers  # lazy: layers imports this module

    path, kernel = forward_execution(mode)
    if path == "pallas" and kernel:
        return ops.paged_decode_attention(q, k_pages, v_pages, block_tables, lengths)
    if path == "pallas":
        with jax.named_scope("PALLAS_FLASH_REGION"):
            return layers.paged_decode_attention_ref(
                q, k_pages, v_pages, block_tables, lengths
            )
    return layers.paged_decode_attention_ref(
        q, k_pages, v_pages, block_tables, lengths
    )


def verify_attention_fwd(
    q: jax.Array,             # [S, T, H, dh] draft window per decode slot
    k_pages: jax.Array,       # [n_pages, page_size, KV, dh] shared page pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, pages_per_slot] int32 physical page ids
    lengths: jax.Array,       # [S] int32; window position t attends kpos < lengths+t
    *,
    mode: str = "auto",
) -> jax.Array:
    """Paged multi-token speculative-verify attention (one verify forward).

    The T-token generalization of :func:`decode_attention_fwd`: every window
    position attends the slot's paged history plus a causal intra-window
    prefix, so one call scores all S×T draft positions.  Same routing
    contract — Pallas path runs the block-table verify kernel
    (kernels/decode_attention), off-TPU auto-detection takes the
    fold-window-into-slots XLA twin inside the PALLAS_FLASH_REGION marker —
    and at T=1 both lowerings reduce bitwise to the decode paths, which is
    what lets the engine promise greedy spec==non-spec token identity.  No
    shard_map wrap, same as decode: the slot axis is not a mesh axis.
    """
    from repro.models import layers  # lazy: layers imports this module

    path, kernel = forward_execution(mode)
    if path == "pallas" and kernel:
        return ops.paged_verify_attention(q, k_pages, v_pages, block_tables, lengths)
    if path == "pallas":
        with jax.named_scope("PALLAS_FLASH_REGION"):
            return layers.paged_verify_attention_ref(
                q, k_pages, v_pages, block_tables, lengths
            )
    return layers.paged_verify_attention_ref(
        q, k_pages, v_pages, block_tables, lengths
    )


def selective_scan_fwd(
    x: jax.Array,      # [B, S, D]
    dt: jax.Array,     # [B, S, D] (softplus'd)
    a: jax.Array,      # [D, N]
    b: jax.Array,      # [B, S, N]
    c: jax.Array,      # [B, S, N]
    h0: jax.Array,     # [B, D, N] f32
    *,
    mode: str = "auto",
    batch_axes: tuple = (),
) -> tuple[jax.Array, jax.Array]:
    """Mamba-1 selective scan for one block: (y [B,S,D] f32, h_last).

    The caller adds the D∘x skip.  Kernel path keeps the [bd, N] state tile
    VMEM-resident for the whole sequence; S == 1 (decode) always takes the
    sequential XLA cell — a one-timestep kernel launch buys nothing.
    """
    from repro.kernels.ref import selective_scan_ref

    path, kernel = forward_execution(mode)
    if x.shape[1] == 1:
        path, kernel = "xla", False
    if path == "pallas" and kernel:
        mesh, ba = _forward_mesh(batch_axes, x.shape[0])
        if mesh is None:
            return ops.selective_scan(x, dt, a, b, c, h0)
        m_ax = _forward_model_axis(mesh, x.shape[2])
        xs = P(ba, None, m_ax)       # x/dt/y: channels ride the model axis
        bc = P(ba, None, None)       # B/C: shared across channels
        hs = P(ba, m_ax, None)       # state: [B, D, N]

        def local_fn(x_l, dt_l, a_l, b_l, c_l, h0_l):
            return ops.selective_scan(x_l, dt_l, a_l, b_l, c_l, h0_l)

        return _shard_call(
            local_fn, mesh,
            (xs, xs, P(m_ax, None), bc, bc, hs), (xs, hs),
            x, dt, a, b, c, h0,
        )
    if path == "pallas":
        with jax.named_scope("PALLAS_FLASH_REGION"):
            return selective_scan_ref(x, dt, a, b, c, h0)
    return selective_scan_ref(x, dt, a, b, c, h0)


def _quant_matmul_ref(x: jax.Array, w: QuantLeaf) -> jax.Array:
    """XLA gather-twin of the fused LUT-dequant matmul: dequantize through
    ``take_along_axis`` (a real gather — the lowering Mosaic can't take,
    which is why the kernel uses select-sum) and contract densely.  The
    dequantized tile values are bit-identical to the kernel's select-sum,
    so kernel-vs-twin parity is a dot-accumulation tolerance, not a
    quantization tolerance."""
    from repro.core.quant import dequantize

    xf = x.astype(jnp.float32)
    wd = dequantize(w).astype(jnp.float32)              # [..., K, N]
    out = jnp.einsum(
        "...k,...kn->...n", xf, wd, preferred_element_type=jnp.float32
    )
    ut = w.qu * w.acc[..., None, :]                      # [..., K, r]
    xu = jnp.einsum(
        "...k,...kr->...r", xf, ut, preferred_element_type=jnp.float32
    )
    out = out + jnp.einsum(
        "...r,...nr->...n", xu, w.qv, preferred_element_type=jnp.float32
    )
    if w.nacc is not None:
        out = out + jnp.einsum(
            "...k,...kn->...n", xf, w.nacc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    return out.astype(x.dtype)


def quant_matmul_fwd(x: jax.Array, w: QuantLeaf, *, mode: str = "auto") -> jax.Array:
    """``x @ W_eff`` for a quantized leaf — the forward half of the
    QuantLeaf protocol (models call this via ``layers.weight_matmul``).

    ``W_eff = dequant(codes) + qu·diag(acc)·qvᵀ [+ nacc]`` is NEVER
    materialized in HBM on the kernel path: the Pallas kernel
    (kernels/quant_matmul) loads the packed b-bit code tile, dequants
    through the per-channel LUT in-tile, and folds the temporal-factor
    delta via the precomputed ``xu = x @ (qu·acc)`` half — so per-pass
    weight traffic is the packed codes (b/16 of the bf16 bytes) plus
    r-fraction noise.  Off-TPU the XLA gather-twin runs inside the
    ``PALLAS_FLASH_REGION`` marker, same costing convention as the other
    forward kernels.  The MeZO-family ``nacc`` delta (dense, trainable)
    is applied as a separate XLA matmul on both paths — it is state
    traffic, not weight-materialization traffic.

    No shard_map wrap: the call sites sit under the model's ``lax.scan``
    with per-layer (unbatched) leaves; a tensor-parallel sharded quant
    forward on a real mesh is a follow-on: a multi-chip jit refuses a
    Mosaic kernel outside ``shard_map`` ("cannot be automatically
    partitioned"), so today the kernel path runs on one chip only.
    Batched leaves always take the twin.
    """
    path, kernel = forward_execution(mode)
    if path == "pallas" and kernel and w.codes.ndim == 2:
        lead = x.shape[:-1]
        x2 = x.reshape((-1, x.shape[-1]))
        xf = x2.astype(jnp.float32)
        ut = (w.qu * w.acc[..., None, :]).astype(jnp.float32)
        xu = jnp.dot(xf, ut, preferred_element_type=jnp.float32)
        out = ops.quant_matmul(
            x2, w.codes, scaled_lut(w), xu, w.qv, bits=w.bits
        )
        if w.nacc is not None:
            out = (
                out.astype(jnp.float32)
                + jnp.dot(
                    xf, w.nacc.astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                )
            ).astype(x.dtype)
        return out.reshape(lead + (out.shape[-1],))
    if path == "pallas":
        with jax.named_scope("PALLAS_FLASH_REGION"):
            return _quant_matmul_ref(x, w)
    return _quant_matmul_ref(x, w)
