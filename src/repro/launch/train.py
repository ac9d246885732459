"""End-to-end ZO fine-tuning driver.

Single-host execution of the same step that the dry-run lowers for the
production meshes: build model -> init/restore -> jit ZO step (scalar-κ DP
by construction) -> loop with prefetch, periodic eval, async checkpoints,
straggler simulation, and crash-safe restart.

    PYTHONPATH=src python -m repro.launch.train \
        --arch opt-125m --smoke --method tezo_adam --steps 300

``--mesh host:D,M`` runs sharded over the first D·M devices: the chips of
a TPU host, or on CPU fake host devices (set
XLA_FLAGS=--xla_force_host_platform_device_count=N first) — used by the
multi-device integration tests; default is single-device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_config, get_smoke_config
from repro.core import AdaptiveQ, ZOConfig, build_zo_train_step, init_zo_state
from repro.core import kernel_execution, zo_pass_count
from repro.core.dispatch import shard_context
from repro.core.rank import select_ranks
from repro.data import DataConfig, Prefetcher, batch_at_step
from repro.distributed import (
    StragglerSim,
    batch_shardings,
    build_ensemble_zo_train_step,
    param_spec_table,
    replicated_tree,
    zo_state_shardings,
)
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.optim import adamw, build_fo_train_step, init_fo_state


def train(
    arch: str = "opt-125m",
    smoke: bool = False,
    method: str = "tezo_adam",
    kernel_mode: str = "auto",
    steps: int = 300,
    seq_len: int = 128,
    global_batch: int = 8,
    lr: float = 1e-6,
    rho: float = 1e-3,
    rank: int = 24,
    rank_mode: str = "const",
    weight_quant: str = "none",
    q_probes: int = 1,
    restore_mode: str = "inplace",
    probe_parallel: bool = False,
    adaptive_q: bool = False,
    q_max: int = 16,
    seed: int = 0,
    ckpt_dir: str | None = None,
    ckpt_every: int = 100,
    eval_every: int = 50,
    log_every: int = 10,
    mesh=None,
    ensemble: int = 0,
    straggler_prob: float = 0.0,
    pretrain_steps: int = 0,
    pretrain_lr: float = 3e-3,
    data_cfg: DataConfig | None = None,
    log_file: str | None = None,
    verbose: bool = True,
) -> dict:
    cfg = (get_smoke_config(arch) if smoke else get_config(arch))
    # one knob rules the whole step: the ZO kernel_mode also selects the
    # forward compute lowering (flash attention / selective scan dispatch)
    cfg = cfg.reduced(kernel_mode=kernel_mode)
    model = build_model(cfg)
    data = data_cfg or DataConfig(
        seq_len=seq_len, global_batch=global_batch,
        vocab_size=min(cfg.vocab_size, 512), seed=seed,
    )

    zo_cfg = ZOConfig(
        method=method, kernel_mode=kernel_mode, lr=lr, rho=rho, rank=rank,
        rank_mode=rank_mode, weight_quant=weight_quant, q_probes=q_probes,
        restore_mode=restore_mode, probe_parallel=probe_parallel,
        adaptive_q=adaptive_q, q_max=q_max, seed=seed, total_steps=steps,
    )
    if probe_parallel and (mesh is None or "data" not in mesh.axis_names):
        raise ValueError(
            "--probe-parallel requires --mesh with a data axis (the q probes "
            "shard over the mesh's data-axis replicas)"
        )
    probe_lanes = (
        dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
        if probe_parallel else None
    )
    # report the lowering that will actually execute (and whether the
    # pallas path is interpret-mode emulation)
    resolved_kernel, kernel_interpret = kernel_execution(method, kernel_mode)
    if kernel_interpret and verbose:
        print(
            "[train] warning: kernel_mode=pallas is running in interpret mode "
            "(no Mosaic on this backend) — correct but slow; walltime is not "
            "a fused-kernel measurement",
            flush=True,
        )
    key = jax.random.PRNGKey(seed)
    params = model.init(key)

    # optional FO pretraining so ZO starts from a sensible point (the paper
    # fine-tunes pretrained checkpoints; examples use this to mimic that)
    if pretrain_steps > 0:
        opt = adamw(lr=pretrain_lr)
        fo_state = init_fo_state(params, opt)
        fo_step = jax.jit(build_fo_train_step(model.loss_fn, opt))
        for s in range(pretrain_steps):
            batch = {k: jnp.asarray(w) for k, w in batch_at_step(data, 10_000_000 + s).items()}
            fo_state, m = fo_step(fo_state, batch)
        params = fo_state.params
        del fo_state

    ranks = masks = None
    if zo_cfg.rank_mode == "spectral":
        ranks, masks = select_ranks(
            params, threshold=zo_cfg.rank_threshold, r_max=zo_cfg.r_max
        )
    state = init_zo_state(params, zo_cfg, ranks, masks)

    state_sh = None
    if mesh is not None:
        # Mesh runs need sharding-invariant jax.random streams so the dense-
        # fallback leaves (biases/norm scales) draw the same z as the
        # single-device reference — the counter-PRNG kernel leaves are
        # mesh-invariant by construction (see core.dispatch).
        jax.config.update("jax_threefry_partitionable", True)
        if probe_parallel:
            # probe-parallel lanes evaluate their probe block on the full
            # replicated (params, batch, mstate) view — the data axis holds
            # probe replicas, not batch shards (core.zo_step)
            state_sh = replicated_tree(mesh, jax.eval_shape(lambda: state))
        else:
            state_sh = zo_state_shardings(
                mesh, model.logical_axes(), jax.eval_shape(lambda: state)
            )

    # mesh + the per-leaf spec table turn on shard-aware kernel dispatch:
    # each leaf's fused perturb/update, and each forward kernel, runs under
    # shard_map on its local shard — a Mosaic kernel has no GSPMD rule, so on
    # a real multi-chip mesh it must.  Probe-parallel passes an empty spec
    # table: every leaf is replicated and the leaf ops run their plain
    # lowerings.
    param_specs = None
    if state_sh is not None:
        param_specs = {} if probe_parallel else param_spec_table(state_sh.params)

    if ensemble > 1:
        if probe_parallel:
            raise ValueError("--probe-parallel does not compose with --ensemble")
        if adaptive_q:
            raise ValueError("--adaptive-q does not compose with --ensemble")
        sim = StragglerSim(ensemble, straggler_prob, seed=seed + 99)
        step_fn = build_ensemble_zo_train_step(
            model.loss_fn, zo_cfg, ensemble,
            straggler_mask_fn=sim.mask_fn() if straggler_prob > 0 else None,
        )
    else:
        def build_step(cfg_b):
            return build_zo_train_step(
                model.loss_fn, cfg_b, mesh=mesh, param_specs=param_specs
            )

        step_fn = build_step(zo_cfg)

    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if ckpt and ckpt.latest_step() is not None:
        template = jax.eval_shape(lambda: state)
        state, extra = ckpt.restore(template, shardings=state_sh)
        start_step = int(extra.get("step", int(state.step)))
        print(f"[train] restored step {start_step} from {ckpt.dir}")

    if mesh is not None:
        batch_abs = jax.eval_shape(
            lambda: {k: jnp.asarray(v) for k, v in batch_at_step(data, 0).items()}
        )
        batch_sh = (
            replicated_tree(mesh, batch_abs) if probe_parallel
            else batch_shardings(mesh, batch_abs)
        )

        def jit_step(fn):
            return jax.jit(
                fn,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )

        state = jax.device_put(state, state_sh)
    else:
        def jit_step(fn):
            return jax.jit(fn, donate_argnums=(0,))

    step_fn = jit_step(step_fn)

    def eval_loss(params, batch):
        with shard_context(mesh, param_specs):
            return model.loss_fn(params, batch)

    eval_fn = jax.jit(eval_loss)
    eval_batch = {k: jnp.asarray(v) for k, v in batch_at_step(data, 999_999_999).items()}

    controller = (
        AdaptiveQ(q=zo_cfg.q_probes, q_max=zo_cfg.q_max)
        if zo_cfg.adaptive_q else None
    )
    prefetch = Prefetcher(data, start_step=start_step)
    history: list[dict] = []
    # the window holds UNFETCHED device arrays: a float() per step would
    # block on the device stream every iteration (the async dispatch pipeline
    # drains to one step deep); everything materializes in one device_get at
    # the log boundary instead
    losses_window: list[jax.Array] = []
    t_start = time.time()
    try:
        for step_idx, host_batch in prefetch:
            if step_idx >= steps:
                break
            batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
            # ENFORCED no-host-sync invariant: any implicit device→host
            # materialization in the steady-state segment (a float() on a
            # metric, an np.asarray on the loss) raises here instead of
            # silently serializing dispatch; fetches belong in the
            # log-boundary block below (explicit device_get stays legal)
            with jax.transfer_guard_device_to_host("disallow"):
                state, metrics = step_fn(state, batch)
                losses_window.append(metrics["loss"])
            if (step_idx + 1) % log_every == 0:
                window = np.asarray(jax.device_get(losses_window), np.float32)
                rec = {
                    "step": step_idx + 1,
                    "loss": float(np.mean(window)),
                    "kappa_abs": float(metrics["kappa_abs"]),
                    "wall_s": round(time.time() - t_start, 1),
                }
                losses_window.clear()
                if controller is not None:
                    new_q = controller.observe(
                        float(metrics["kappa_var"]), rec["kappa_abs"]
                    )
                    if new_q is not None:
                        # grow the probe ensemble (AdaZeta schedule): the
                        # step is static in q, so growth = rebuild + re-jit
                        # here at the log boundary
                        zo_cfg = dataclasses.replace(zo_cfg, q_probes=new_q)
                        step_fn = jit_step(build_step(zo_cfg))
                        rec["q_probes"] = new_q
                if (step_idx + 1) % eval_every == 0:
                    rec["eval_loss"] = float(eval_fn(state.params, eval_batch))
                history.append(rec)
                if verbose:
                    print(f"[train] {json.dumps(rec)}", flush=True)
            if ckpt and (step_idx + 1) % ckpt_every == 0:
                ckpt.save_async(step_idx + 1, state, extra={"step": step_idx + 1})
    finally:
        prefetch.close()
        if ckpt:
            ckpt.wait()

    final_eval = float(eval_fn(state.params, eval_batch))
    result = {
        "arch": cfg.name,
        "method": method,
        "kernel_mode": resolved_kernel,
        "kernel_interpret": kernel_interpret,
        "steps": steps,
        # step-schedule provenance: the chained default makes 2q+1 full-W
        # passes per step; probe-parallel records the busiest lane's
        # 2·ceil(q/D)+1 per-replica passes (see repro.core.zo_step).
        # q_probes is the FINAL ensemble size (adaptive-q may have grown it).
        "q_probes": zo_cfg.q_probes,
        "restore_mode": restore_mode,
        "weight_quant": weight_quant,
        "probe_parallel": probe_parallel,
        "probe_lanes": probe_lanes,
        "zo_passes": zo_pass_count(
            zo_cfg.q_probes, restore_mode, probe_lanes=probe_lanes
        ),
        "final_eval_loss": final_eval,
        # where the final params really live: the most devices one leaf
        # spans, and how many leaves are split (not replicated) across them
        "param_devices": max(
            len(a.sharding.device_set) for a in jax.tree.leaves(state.params)
        ),
        "sharded_param_leaves": sum(
            a.sharding.shard_shape(a.shape) != a.shape
            for a in jax.tree.leaves(state.params)
        ),
        "history": history,
        "wall_s": round(time.time() - t_start, 1),
    }
    if log_file:
        Path(log_file).parent.mkdir(parents=True, exist_ok=True)
        Path(log_file).write_text(json.dumps(result, indent=1))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-125m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--method", default="tezo_adam")
    ap.add_argument(
        "--kernel-mode", default="auto", choices=["auto", "pallas", "xla"],
        help="fused Pallas kernels vs dense XLA for the ZO hot path — all "
        "nine methods route through the dispatch layer (auto: pallas on "
        "TPU, xla elsewhere).  NB the MeZO family's pallas path draws its "
        "noise from the on-chip counter PRNG, a different stream than the "
        "xla path (statistically identical, not bitwise)",
    )
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-6)
    ap.add_argument("--rho", type=float, default=1e-3)
    ap.add_argument("--rank", type=int, default=24)
    ap.add_argument("--rank-mode", default="const", choices=["const", "spectral"])
    ap.add_argument(
        "--weight-quant", default="none",
        choices=["none", "nf4", "lut3", "lut4"],
        help="store transformer block weights as packed LUT-quantized leaves "
        "(core.quant.QuantLeaf): 3/4-bit codes + per-channel codebooks in "
        "HBM, dequantized in-tile on the forward path; TeZO-family "
        "perturb/update then move only the r-vector temporal coefficient — "
        "zero weight bytes per ZO pass.  Composes with tezo/tezo_m/"
        "tezo_adam/mezo/mezo_m/mezo_adam; requires weight_decay 0",
    )
    ap.add_argument("--q-probes", type=int, default=1)
    ap.add_argument(
        "--restore-mode", default="inplace",
        choices=["inplace", "unchained", "exact"],
        help="step schedule: inplace = the chained transitions (2q+1 full-W "
        "passes — bridge fuses restore_i with perturb_{i+1}, the update "
        "absorbs the last restore); unchained = literal Algorithm 1 "
        "(3q+1 passes, numerical studies); exact = branch ±ρ copies off "
        "the originals (bit-exact restore, 2× transient memory)",
    )
    ap.add_argument(
        "--probe-parallel", action="store_true",
        help="shard the q probes over the mesh's data axis: D replicas each "
        "run a disjoint probe block concurrently (2·ceil(q/D)+1 per-replica "
        "passes instead of 2q+1) and one psum of 2q scalars completes the "
        "step — bitwise identical to the sequential chained schedule; "
        "requires --mesh with a data axis and restore-mode inplace",
    )
    ap.add_argument(
        "--adaptive-q", action="store_true",
        help="AdaZeta-style probe growth: double q_probes (up to --q-max) "
        "when the κ-variance EMA says the estimator is noise-dominated; "
        "host-level, re-jits the step at log boundaries",
    )
    ap.add_argument("--q-max", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--pretrain-steps", type=int, default=0)
    ap.add_argument("--ensemble", type=int, default=0)
    ap.add_argument("--straggler-prob", type=float, default=0.0)
    ap.add_argument("--log-file", default=None)
    ap.add_argument(
        "--mesh", default=None, metavar="host:D,M",
        help="run the step sharded on a D×M (data, model) mesh over the "
        "first D·M devices — the chips of a TPU host, or on CPU fake host "
        "devices (set XLA_FLAGS=--xla_force_host_platform_device_count=N, "
        "N ≥ D·M, before launch); under --kernel-mode pallas the dispatch is "
        "shard-aware (shard_map over local shards, mesh-invariant noise "
        "streams)",
    )
    args = ap.parse_args()
    use_compile_cache()
    kwargs = {k.replace("-", "_"): v for k, v in vars(args).items()}
    mesh_arg = kwargs.pop("mesh", None)
    if mesh_arg is not None:
        from repro.launch.mesh import make_host_mesh

        kind, _, dims = mesh_arg.partition(":")
        if kind != "host" or not dims:
            raise SystemExit(f"--mesh expects host:D,M, got {mesh_arg!r}")
        d, m = (int(x) for x in dims.split(","))
        kwargs["mesh"] = make_host_mesh(data=d, model=m)
    result = train(**kwargs)
    print(json.dumps({k: v for k, v in result.items() if k != "history"}, indent=1))


if __name__ == "__main__":
    main()
