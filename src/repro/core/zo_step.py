"""The ZO training step: Algorithm 1 of the paper as a *perturbation chain*.

Algorithm 1 evaluates ±ρ probes and updates:

    W ← W + ρZ ;  f₊ ;  W ← W − 2ρZ ;  f₋ ;  W ← W + ρZ (restore) ;  update

Naively that is ``3q + 1`` full-parameter HBM passes for ``q`` probes even
when every individual pass is a fused one-round-trip kernel — and ZO
fine-tuning has no backward pass, so those weight sweeps are the step's
entire non-forward walltime.  But adjacent passes apply known linear
combinations of *reconstructible* Z's (Z is a pure function of the step key
— MeZO's resampling trick), so the step is emitted here as **transitions**:

    first_perturb        W ← W + ρZ₀                          (1 pass)
    flip                 W ← W − 2ρZ_i                        (q passes)
    bridge               W ← W + ρZ_i + ρZ_{i+1}              (q − 1 passes)
                         — the restore of probe i FUSED with the perturb of
                         probe i+1, one pass instead of two
    restore_into_update  W ← optimizer(W + ρZ_{q−1})          (1 pass)
                         — the last restore folded into the fused update
                         kernels via their ``restore_*`` operands

Total: ``2q + 1`` full-parameter passes (q=1: 4→3, q=4: 13→9).  Every
method implements the transitions through ``ZOMethod.perturb_pair`` and
``ZOMethod.update(..., restore_probe=, restore_scale=)`` (see
repro.core.estimator); the fused leaf ops reproduce the weight-dtype
rounding of each pass they merge, so the chained trajectory is **bitwise
identical** to the unchained one — for the factor methods on both
lowerings, and for the MeZO family within each lowering, where chained and
unchained regenerate identical per-probe counter streams (the dual-draw
bridge kernel draws z_i and z_{i+1} from the same counters in one tile
visit — bitwise the same draws, not merely the same distribution).

``cfg.restore_mode`` selects the schedule:

  "inplace"    (default) the chained transitions above — 2q+1 passes, one
               parameter-sized buffer live (XLA reuses the donated buffer).
  "unchained"  the literal Algorithm-1 pass structure — 3q+1 passes, kept
               for numerical studies and as the chained path's bitwise
               reference (tests/test_chain_fusion.py).
  "exact"      branch the ±ρ copies off the original params — 2q+1 passes
               at 2× transient memory, bit-exact restore by construction.

``zo_pass_count(q, restore_mode)`` is the canonical pass-count model; the
benchmarks' bytes-moved model, the dry-run record, and the kernel-invocation
spy test all consume it.

**Probe-parallel schedule** (``cfg.probe_parallel``, requires
``restore_mode == "inplace"`` and a mesh with a "data" axis): the D
replicas on the data axis each evaluate a disjoint *contiguous block* of
the q probes concurrently instead of walking all q sequentially.  A probe's
only contribution to the update is the scalar pair (f₊, f₋) — and Z is
reconstructible from (leaf key, probe, global coordinates) under the PRNG
contract — so lane d starting its block at probe s first replays probes
0..s−1's ±ρ triples as ONE fused catch-up chain (``ZOMethod.
perturb_chain``: 3s+1 deltas, one HBM pass), then runs its block's
bridge/flip transitions exactly like the sequential chain.  The step
``psum``s a probe-indexed [q, 2] loss matrix over the data axis (each entry
written by exactly one lane, so the fixed probe-indexed reduction order is
exact — zeros add bitwise-neutrally), rebuilds κ in probe order, and runs
ONE fused update pass on the *original* params whose restore operand
replays the whole 3q-delta trajectory ((i,+ρ),(i,−2ρ),(i,+ρ) for i=0..q−1).
Because every delta round-trips through the weight dtype exactly as its own
pass would, regrouping the same delta sequence into different passes is
bitwise-invariant — the probe-parallel step matches the sequential chained
step bit for bit (locked by tests/test_probe_parallel_{tezo,mezo,lozo}.py).

Per-replica pass count: ``zo_pass_count(q, "inplace", probe_lanes=D)`` =
``2·ceil(q/D) + 1`` (catch-up/first-perturb + per-probe flip and bridge +
the shared trajectory-restore update) vs ``2q + 1`` sequential — on D=q
replicas that is 3 passes per replica plus one scalar all-reduce of 2q
floats.

q-SPSA: with cfg.q_probes = q > 1 the step runs q independent ±probes and the
optimizer consumes the κ vector — for TeZO this collapses to the r-vector
mean_i κᵢτᵢ per leaf, i.e. ensemble variance reduction at zero memory.

Kernel dispatch: ``cfg.kernel_mode`` ("auto" | "pallas" | "xla", jit-static)
selects whether the transition leaf ops lower to the fused Pallas kernels or
the dense-reconstruct XLA path — for *every* method (TeZO reconstructs Z
from CPD factors in-tile, MeZO generates z on-chip from a counter PRNG,
LOZO/SubZO reconstruct their factored Z in-tile; see repro.core.dispatch).
The XLA lowering has fused-delta twins for every transition (identical
arithmetic to the unchained dense passes), so parity tests cover both paths.
build_zo_train_step validates kernel_mode AND restore_mode eagerly so a typo
fails at build time, not inside the jitted step.  Note the MeZO-family
caveat: the pallas and xla lowerings draw *different* (equally distributed)
noise streams, so switching kernel_mode changes that baseline's sample path,
not its statistics — but within a lowering, chained and unchained replay the
same streams bitwise.

Sharded execution: pass ``mesh`` + ``param_specs`` (the per-leaf
PartitionSpec table from ``distributed.sharding.param_spec_table``) and the
kernel path wraps each transition leaf op in shard_map over that mesh —
local-shard Pallas kernels with a mesh-layout-invariant noise stream (the
dual-draw and restore-fused kernels carry the same global-coordinate PRNG
contract as the single-draw ops; see the Sharded dispatch section of
repro.core.dispatch).  Without them the Pallas path assumes unsharded
leaves, exactly as before.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.core import dispatch, quant
from repro.core.dispatch import resolve_kernel_mode
from repro.core.estimator import ZOConfig, get_method

RESTORE_MODES = ("inplace", "unchained", "exact")

# Named scopes of the step's phases.  They only add metadata (each
# instruction's op_name) to the compiled step; the benchmark's trace
# reduction (bench/scopes.py) maps device time to them by these names.
SCOPE_BEGIN = "zo.begin"      # method.begin_step (SubZO, LOZO-m; TeZO: none)
SCOPE_PERTURB = "zo.perturb"  # the +rho perturb (first, bridge, catch-up)
SCOPE_FLIP = "zo.flip"        # the -2 rho perturb (the -rho branch in "exact")
SCOPE_UPDATE = "zo.update"    # the restore and the optimizer update


def zo_pass_count(
    q_probes: int, restore_mode: str = "inplace",
    probe_lanes: Optional[int] = None,
) -> int:
    """Full-parameter HBM passes per ZO step (perturb/flip/bridge/update).

    The single source of truth the benchmarks' bytes-moved model, the
    dry-run/train records, and the kernel-invocation spy test share:
    chained "inplace" and branching "exact" make ``2q + 1`` passes,
    the literal Algorithm-1 "unchained" schedule ``3q + 1``.

    With ``probe_lanes`` = D (the probe-parallel schedule: q probes sharded
    over D data-axis replicas) the count is the *per-replica* passes of the
    busiest lane — ``2·ceil(q/D) + 1``: the catch-up chain (or first
    perturb) is one pass, each of the lane's ≤ ceil(q/D) probes costs a
    flip plus (after the first) a bridge, and the trajectory-restore update
    is one shared pass.  Probe-parallel composes only with the "inplace"
    chained schedule.
    """
    if restore_mode not in RESTORE_MODES:
        raise ValueError(
            f"unknown restore_mode {restore_mode!r}; expected one of {RESTORE_MODES}"
        )
    if probe_lanes is not None:
        if restore_mode != "inplace":
            raise ValueError(
                "probe-parallel pass counting requires restore_mode='inplace' "
                f"(got {restore_mode!r})"
            )
        if probe_lanes < 1:
            raise ValueError(f"probe_lanes must be >= 1, got {probe_lanes}")
        return 2 * -(-q_probes // probe_lanes) + 1
    if restore_mode == "unchained":
        return 3 * q_probes + 1
    return 2 * q_probes + 1


@jax.tree_util.register_dataclass
@dataclass
class ZOTrainState:
    params: Any
    mstate: Any
    step: jax.Array      # int32 scalar
    base_key: jax.Array  # PRNG key


def init_zo_state(
    params: Any,
    cfg: ZOConfig,
    ranks: dict | None = None,
    rank_masks: dict | None = None,
) -> ZOTrainState:
    key = jax.random.PRNGKey(cfg.seed)
    method = get_method(cfg.method)
    if cfg.weight_quant != "none":
        if ranks is not None or rank_masks is not None:
            raise ValueError(
                "weight_quant with per-path ranks/rank_masks is unsupported: "
                "quantized leaves draw their factors at cfg.rank before the "
                "method sees the overrides"
            )
        # qu/qv are drawn from the SAME folded key TeZO.init hands to
        # cpd.init_factors (method key, fold 1), so the quantized run's
        # frozen factors — and therefore its Z — equal the dense run's.
        params = quant.quantize_for_config(
            params, cfg, jax.random.fold_in(jax.random.fold_in(key, 0xF0), 1)
        )
    mstate = method.init(params, jax.random.fold_in(key, 0xF0), cfg, ranks, rank_masks)
    return ZOTrainState(
        params=params,
        mstate=mstate,
        step=jnp.zeros((), jnp.int32),
        base_key=jax.random.fold_in(key, 0x5EED),
    )


def build_zo_train_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    cfg: ZOConfig,
    *,
    mesh=None,
    param_specs: Optional[Mapping[str, Any]] = None,
) -> Callable[[ZOTrainState, Any], tuple[ZOTrainState, dict]]:
    """loss_fn(params, batch) -> scalar f32 loss (global mean).

    Under pjit with batch sharded over the data axis, the scalar reduction in
    loss_fn IS the entire data-parallel gradient communication (DESIGN §4:
    scalar-κ DP) — GSPMD emits one f32 all-reduce for it.

    ``mesh`` + ``param_specs`` (path → PartitionSpec; see ``distributed.
    sharding.param_spec_table``) enable shard-aware kernel dispatch: each
    leaf's fused perturb/update runs under shard_map on its local shard.
    They are advisory for the XLA path (GSPMD partitions dense jnp math by
    itself) and required for a correct + local Pallas path on a mesh.
    """
    method = get_method(cfg.method)
    resolve_kernel_mode(cfg.kernel_mode)  # fail fast on unknown modes
    zo_pass_count(cfg.q_probes, cfg.restore_mode)  # …and unknown schedules
    quant.validate_quant_config(cfg)  # …and incompatible weight_quant combos
    if cfg.probe_parallel:
        return _build_probe_parallel_step(
            loss_fn, cfg, method, mesh=mesh, param_specs=param_specs
        )

    def step_fn(state: ZOTrainState, batch: Any) -> tuple[ZOTrainState, dict]:
        with dispatch.shard_context(mesh, param_specs):
            key_t = jax.random.fold_in(state.base_key, state.step)
            with jax.named_scope(SCOPE_BEGIN):
                mstate = method.begin_step(state.mstate, key_t, state.step, cfg)
            lr = cfg.schedule(state.step)

            params = state.params
            rho = cfg.rho
            kappas = []
            f_plus_acc = jnp.zeros((), jnp.float32)
            f_minus_acc = jnp.zeros((), jnp.float32)
            p = params
            for probe in range(cfg.q_probes):
                if cfg.restore_mode == "exact":
                    # branch ±ρ copies off the original params (bit-exact
                    # restore, 2× transient memory)
                    with jax.named_scope(SCOPE_PERTURB):
                        p_plus = method.perturb(params, mstate, key_t, probe, +rho, cfg, state.step)
                    f_plus = loss_fn(p_plus, batch)
                    with jax.named_scope(SCOPE_FLIP):
                        p_minus = method.perturb(params, mstate, key_t, probe, -rho, cfg, state.step)
                    f_minus = loss_fn(p_minus, batch)
                elif cfg.restore_mode == "unchained":
                    # the literal Algorithm-1 in-place schedule: restore and
                    # next-probe perturb are separate full-W passes
                    with jax.named_scope(SCOPE_PERTURB):
                        p = method.perturb(params, mstate, key_t, probe, +rho, cfg, state.step)
                    f_plus = loss_fn(p, batch)
                    with jax.named_scope(SCOPE_FLIP):
                        p = method.perturb(p, mstate, key_t, probe, -2.0 * rho, cfg, state.step)
                    f_minus = loss_fn(p, batch)
                    with jax.named_scope(SCOPE_UPDATE):
                        params = method.perturb(p, mstate, key_t, probe, +rho, cfg, state.step)
                else:  # "inplace": the chained transitions
                    with jax.named_scope(SCOPE_PERTURB):
                        if probe == 0:
                            p = method.perturb(p, mstate, key_t, 0, +rho, cfg, state.step)
                        else:
                            # bridge: restore probe−1 and perturb probe, one pass
                            p = method.perturb_pair(
                                p, mstate, key_t,
                                probe - 1, +rho, probe, +rho, cfg, state.step,
                            )
                    f_plus = loss_fn(p, batch)
                    with jax.named_scope(SCOPE_FLIP):
                        p = method.perturb(p, mstate, key_t, probe, -2.0 * rho, cfg, state.step)
                    f_minus = loss_fn(p, batch)
                kappas.append((f_plus - f_minus) / (2.0 * rho))
                f_plus_acc = f_plus_acc + f_plus
                f_minus_acc = f_minus_acc + f_minus

            kappa_vec = jnp.stack(kappas).astype(jnp.float32)
            with jax.named_scope(SCOPE_UPDATE):
                if cfg.restore_mode == "inplace":
                    # restore_into_update: the last probe's +ρZ restore rides
                    # the fused update pass
                    params, mstate = method.update(
                        p, mstate, key_t, kappa_vec, lr, cfg, state.step,
                        restore_probe=cfg.q_probes - 1, restore_scale=+rho,
                    )
                else:
                    params, mstate = method.update(
                        params, mstate, key_t, kappa_vec, lr, cfg, state.step
                    )

        new_state = ZOTrainState(
            params=params,
            mstate=mstate,
            step=state.step + 1,
            base_key=state.base_key,
        )
        q = float(cfg.q_probes)
        metrics = {
            "loss": (f_plus_acc + f_minus_acc) / (2.0 * q),
            "kappa_abs": jnp.mean(jnp.abs(kappa_vec)),
            # κ dispersion across the probe ensemble — the adaptive-q
            # controller's signal (core.adaptive); cheap (q scalars)
            "kappa_var": jnp.var(kappa_vec),
            "lr": lr,
            # static per config, surfaced so step records are self-describing
            "zo_passes": jnp.asarray(
                zo_pass_count(cfg.q_probes, cfg.restore_mode), jnp.int32
            ),
        }
        return new_state, metrics

    return step_fn


def _build_probe_parallel_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    cfg: ZOConfig,
    method,
    *,
    mesh=None,
    param_specs: Optional[Mapping[str, Any]] = None,
) -> Callable[[ZOTrainState, Any], tuple[ZOTrainState, dict]]:
    """The probe-parallel transition schedule (see module docstring).

    Probe phase: one full-manual shard_map over the whole mesh — every
    device holds the full replicated (params, batch, mstate) view, takes the
    branch of its data-axis lane (static probe block via ``lax.switch``),
    and contributes its block's (f₊, f₋) rows to a probe-indexed [q, 2]
    matrix that one ``psum`` over the data axis completes.  The dispatch
    shard context is cleared inside the manual region (the leaf ops run
    their plain unsharded lowerings on the full view — a nested shard_map
    cannot partition further).  Update phase: back under the outer shard
    context, one fused shard-aware update pass on the ORIGINAL params whose
    restore operand replays the whole 3q-delta trajectory.
    """
    if cfg.restore_mode != "inplace":
        raise ValueError(
            "probe_parallel requires restore_mode='inplace' (the chained "
            f"schedule); got restore_mode={cfg.restore_mode!r}"
        )
    if mesh is None or "data" not in mesh.axis_names:
        raise ValueError(
            "probe_parallel requires a mesh with a 'data' axis (got "
            f"{None if mesh is None else mesh.axis_names})"
        )
    from repro.distributed.collectives import probe_assignment
    from jax.sharding import PartitionSpec as P

    lanes = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
    starts, counts = probe_assignment(cfg.q_probes, lanes)
    per_replica_passes = zo_pass_count(
        cfg.q_probes, cfg.restore_mode, probe_lanes=lanes
    )
    q = cfg.q_probes
    rho = cfg.rho

    def step_fn(state: ZOTrainState, batch: Any) -> tuple[ZOTrainState, dict]:
        with dispatch.shard_context(mesh, param_specs):
            key_t = jax.random.fold_in(state.base_key, state.step)
            with jax.named_scope(SCOPE_BEGIN):
                mstate = method.begin_step(state.mstate, key_t, state.step, cfg)
            lr = cfg.schedule(state.step)

            def lane_body(params_r, batch_r, mstate_r, key_r, step_r):
                # the manual region: full replicated views, plain unsharded
                # leaf-op lowerings (shard context cleared for the duration)
                with dispatch.shard_context(None, None):
                    lane = jax.lax.axis_index("data")

                    def branch(d):
                        start, count = starts[d], counts[d]

                        def run(_):
                            out = jnp.zeros((q, 2), jnp.float32)
                            if count == 0:
                                # more lanes than probes: idle contributor
                                return out
                            with jax.named_scope(SCOPE_PERTURB):
                                if start == 0:
                                    p = method.perturb(
                                        params_r, mstate_r, key_r, 0, +rho,
                                        cfg, step_r,
                                    )
                                else:
                                    # catch-up: replay probes 0..start−1's
                                    # ±ρ triples and open probe `start`, one
                                    # pass
                                    chain_p = tuple(
                                        j for i in range(start)
                                        for j in (i, i, i)
                                    ) + (start,)
                                    chain_s = tuple(
                                        s for _ in range(start)
                                        for s in (+rho, -2.0 * rho, +rho)
                                    ) + (+rho,)
                                    p = method.perturb_chain(
                                        params_r, mstate_r, key_r,
                                        chain_p, chain_s, cfg, step_r,
                                    )
                            for j in range(count):
                                probe = start + j
                                if j > 0:
                                    with jax.named_scope(SCOPE_PERTURB):
                                        p = method.perturb_pair(
                                            p, mstate_r, key_r,
                                            probe - 1, +rho, probe, +rho,
                                            cfg, step_r,
                                        )
                                f_plus = loss_fn(p, batch_r)
                                with jax.named_scope(SCOPE_FLIP):
                                    p = method.perturb(
                                        p, mstate_r, key_r, probe,
                                        -2.0 * rho, cfg, step_r,
                                    )
                                f_minus = loss_fn(p, batch_r)
                                out = out.at[probe, 0].set(
                                    f_plus.astype(jnp.float32)
                                )
                                out = out.at[probe, 1].set(
                                    f_minus.astype(jnp.float32)
                                )
                            return out

                        return run

                    contrib = jax.lax.switch(
                        lane, [branch(d) for d in range(lanes)], 0
                    )
                    # each [probe, ±] entry has exactly one nonzero writer
                    # (disjoint blocks), so this fixed probe-indexed psum is
                    # exact — the other lanes contribute bitwise-neutral 0s
                    return jax.lax.psum(contrib, "data")

            f_mat = jax.shard_map(
                lane_body, mesh=mesh,
                in_specs=(P(), P(), P(), P(), P()),
                out_specs=P(), check_vma=False,
            )(state.params, batch, mstate, key_t, state.step)

            # κ and the loss accumulators rebuilt in probe-index order with
            # the sequential schedule's exact op sequence (left folds from
            # f32 zero) — bitwise-identical metrics
            kappas = []
            f_plus_acc = jnp.zeros((), jnp.float32)
            f_minus_acc = jnp.zeros((), jnp.float32)
            for i in range(q):
                f_plus, f_minus = f_mat[i, 0], f_mat[i, 1]
                kappas.append((f_plus - f_minus) / (2.0 * rho))
                f_plus_acc = f_plus_acc + f_plus
                f_minus_acc = f_minus_acc + f_minus
            kappa_vec = jnp.stack(kappas).astype(jnp.float32)

            # ONE fused update pass on the ORIGINAL params: the restore
            # operand replays the full 3q-delta trajectory, each delta
            # rounding through the weight dtype exactly as its own pass
            # would — bitwise identical to the sequential chained update
            restore_probes = tuple(i for i in range(q) for _ in range(3))
            restore_scales = tuple(
                s for _ in range(q) for s in (+rho, -2.0 * rho, +rho)
            )
            with jax.named_scope(SCOPE_UPDATE):
                params, mstate = method.update(
                    state.params, mstate, key_t, kappa_vec, lr, cfg,
                    state.step, restore_probe=restore_probes,
                    restore_scale=restore_scales,
                )

        new_state = ZOTrainState(
            params=params,
            mstate=mstate,
            step=state.step + 1,
            base_key=state.base_key,
        )
        metrics = {
            "loss": (f_plus_acc + f_minus_acc) / (2.0 * float(q)),
            "kappa_abs": jnp.mean(jnp.abs(kappa_vec)),
            "kappa_var": jnp.var(kappa_vec),
            "lr": lr,
            # per-replica passes of the busiest lane (the walltime model) —
            # NOT the sequential 2q+1; plus one scalar all-reduce of 2q f32
            "zo_passes": jnp.asarray(per_replica_passes, jnp.int32),
        }
        return new_state, metrics

    return step_fn


def build_eval_step(
    loss_fn: Callable[[Any, Any], jax.Array],
) -> Callable[[Any, Any], jax.Array]:
    def eval_fn(params: Any, batch: Any) -> jax.Array:
        return loss_fn(params, batch)

    return eval_fn
