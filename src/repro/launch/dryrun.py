import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
record memory / cost / collective analysis (EXPERIMENTS.md §Dry-run).

The lines above MUST stay the first statements in this file — jax locks
the device count and the platform on first init.  The dry-run is a CPU
tool: its meshes are fake host devices, so it never takes a TPU.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]

Each cell writes results/dryrun/<arch>__<shape>__<mesh>[__tag].json.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

# Every dry-run cell lowers on a production mesh; sharding-invariant
# jax.random streams keep the dense-fallback ZO leaves' noise identical to
# single-device execution (the kernel leaves are invariant by construction).
jax.config.update("jax_threefry_partitionable", True)

from repro.configs import SHAPES, get_config, runnable_cells
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import (
    KERNEL_METHODS,
    ZOConfig,
    build_zo_train_step,
    init_zo_state,
    kernel_execution,
    zo_pass_count,
)
from repro.distributed.sharding import (
    batch_axes,
    batch_shardings,
    cache_shardings,
    param_shardings,
    param_spec_table,
    zo_state_shardings,
)
from repro.launch.hlo_analysis import analyze_hlo, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.utils.tree import tree_num_params, tree_size_bytes


def active_params(cfg: ModelConfig, model) -> float:
    """Analytic active-parameter count (MoE: k/E of expert params)."""
    total = tree_num_params(model.abstract_params())
    if cfg.n_experts == 0:
        return float(total)
    L, E, D, F = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_ff
    expert_total = L * E * 3 * D * F
    active_experts = expert_total * cfg.n_experts_per_token / cfg.n_experts
    return float(total - expert_total + active_experts)


def model_flops(cfg: ModelConfig, model, shape: ShapeConfig, zo: bool) -> dict:
    """Analytic MODEL_FLOPS conventions (§Roofline): 6·N·D train (FO), and the
    ZO-faithful 4·N·D (two forwards, no backward).  Attention term added
    explicitly; decode counts one token per sequence."""
    n_active = active_params(cfg, model)
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        tokens = B * S
        kv_span = min(S, cfg.window) if cfg.window > 0 else S
        attn = 2.0 * 2.0 * B * S * kv_span / 2 * cfg.n_heads * cfg.head_dim
        fwd = 2.0 * n_active * tokens + attn
        return {
            "model_flops_6nd": 3.0 * fwd if not zo else 3.0 * fwd,  # fwd+bwd conv.
            "model_flops_step": (2.0 * fwd) if zo else (3.0 * fwd),
            "tokens": tokens,
            "n_active": n_active,
        }
    if shape.kind == "prefill":
        tokens = B * S
        kv_span = min(S, cfg.window) if cfg.window > 0 else S
        attn = 2.0 * 2.0 * B * S * kv_span / 2 * cfg.n_heads * cfg.head_dim
        fwd = 2.0 * n_active * tokens + attn
        return {"model_flops_6nd": fwd, "model_flops_step": fwd,
                "tokens": tokens, "n_active": n_active}
    # decode: one token, attention over the live cache
    kv_span = min(S, cfg.window) if cfg.window > 0 else S
    attn = 2.0 * 2.0 * B * kv_span * cfg.n_heads * cfg.head_dim
    fwd = 2.0 * n_active * B + attn
    return {"model_flops_6nd": fwd, "model_flops_step": fwd,
            "tokens": B, "n_active": n_active}


def _mem_stats(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        return {
            k: int(getattr(ma, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            )
        }
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    method: str = "tezo_adam",
    rank: int = 64,
    out_dir: str = "results/dryrun",
    tag: str = "",
    overrides: dict | None = None,
    verbose: bool = True,
    save_hlo: bool = False,
    kernel_mode: str = "auto",
    weight_quant: str = "none",
) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.distributed.context import set_current_mesh

    set_current_mesh(mesh)
    n_devices = mesh.devices.size
    shape = SHAPES[shape_name]
    overrides = dict(overrides or {})
    ba = overrides.pop("batch_axis_names", None)
    if ba is not None and multi_pod:
        ba = ("pod",) + tuple(a for a in ba if a != "pod")
    # kernel_mode reaches the model config too: the forward compute (flash
    # attention / selective scan) dispatches on it, for every cell kind —
    # explicit per-preset overrides still win.
    cfg = get_config(arch).reduced(
        spmd_hints=True,
        batch_axis_names=ba or batch_axes(mesh),
        **{"kernel_mode": kernel_mode, **overrides},
    )
    model = build_model(cfg)
    axes = model.logical_axes()
    record: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "mesh_shape": list(mesh.devices.shape),
        "n_devices": int(n_devices),
        "method": method,
        "tag": tag,
        "params_total": int(tree_num_params(model.abstract_params())),
        "params_bytes_global": int(tree_size_bytes(model.abstract_params())),
    }

    t0 = time.time()
    if shape.kind != "train":
        # serving cells run no ZO step but their forward still dispatches:
        # record the forward lowering (off-TPU "pallas" is the marker-region
        # XLA twin, costed with the kernel HBM model by analyze_hlo)
        from repro.core.dispatch import forward_execution

        fwd_path, fwd_kernel = forward_execution(cfg.kernel_mode)
        record["kernel_mode"] = fwd_path
        if fwd_path == "pallas":
            record["forward_kernel_executed"] = fwd_kernel
    if shape.kind == "train":
        # every ZO method routes through the kernel dispatch now; mark
        # interpret-mode pallas legs (off-TPU emulation, not Mosaic) so the
        # roofline numbers aren't misread
        resolved, interp = kernel_execution(method, kernel_mode)
        record["kernel_mode"] = resolved
        if resolved == "pallas":
            record["kernel_interpret"] = interp
        # quantized runs keep factors f32 (the QuantLeaf carries qu/qv in
        # f32; see core.quant.validate_quant_config)
        zo_cfg = ZOConfig(
            method=method, kernel_mode=kernel_mode, rank=rank,
            factor_dtype=jnp.float32 if weight_quant != "none" else jnp.bfloat16,
            weight_quant=weight_quant,
        )
        record["weight_quant"] = weight_quant
        # step-schedule provenance: BENCH rows and HLO costings are only
        # comparable across PRs when the record says how many full-W passes
        # the lowered step makes (chained default: 2q+1)
        record["q_probes"] = zo_cfg.q_probes
        record["restore_mode"] = zo_cfg.restore_mode
        # dryrun costs the sequential schedule; probe-parallel provenance is
        # recorded so schema-5 consumers can tell the two apart
        record["probe_parallel"] = zo_cfg.probe_parallel
        record["zo_passes"] = zo_pass_count(zo_cfg.q_probes, zo_cfg.restore_mode)
        state_abs = jax.eval_shape(
            lambda p: init_zo_state(p, zo_cfg), model.abstract_params()
        )
        state_sh = zo_state_shardings(mesh, axes, state_abs)
        batch_abs = model.input_specs(shape)
        batch_sh = batch_shardings(mesh, batch_abs, axes=cfg.batch_axis_names)
        # shard-aware dispatch: under kernel_mode=pallas each leaf op lowers
        # to a shard_map'd local-shard kernel instead of a GSPMD all-gather
        step = build_zo_train_step(
            model.loss_fn, zo_cfg, mesh=mesh,
            param_specs=param_spec_table(state_sh.params),
        )
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        with mesh:
            lowered = jitted.lower(state_abs, batch_abs)
        record["state_bytes_global"] = int(tree_size_bytes(state_abs))
    elif shape.kind == "prefill":
        p_sh = param_shardings(mesh, axes, model.abstract_params())
        batch_abs = model.input_specs(shape)
        del batch_abs["targets"]
        batch_sh = batch_shardings(mesh, batch_abs)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, shape.seq_len)

        jitted = jax.jit(prefill_fn, in_shardings=(p_sh, batch_sh))
        with mesh:
            lowered = jitted.lower(model.abstract_params(), batch_abs)
    else:  # decode
        p_sh = param_shardings(mesh, axes, model.abstract_params())
        dec = model.decode_input_specs(shape)
        cache_abs, tok_abs = dec["cache"], dec["tokens"]
        cache_sh = cache_shardings(mesh, cache_abs)
        tok_sh = batch_shardings(mesh, tok_abs)
        jitted = jax.jit(
            model.decode_step,
            in_shardings=(p_sh, cache_sh, tok_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(1,),
        )
        with mesh:
            lowered = jitted.lower(model.abstract_params(), cache_abs, tok_abs)
    record["lower_s"] = round(time.time() - t0, 2)

    t1 = time.time()
    compiled = lowered.compile()
    record["compile_s"] = round(time.time() - t1, 2)

    # ---- analyses -------------------------------------------------------
    record["memory_analysis"] = _mem_stats(compiled)
    ca = compiled.cost_analysis()
    record["xla_cost"] = {
        "flops": float(ca.get("flops", -1)),
        "bytes_accessed": float(ca.get("bytes accessed", -1)),
    }
    t2 = time.time()
    hlo = compiled.as_text()
    if save_hlo:
        import gzip

        hdir = Path(out_dir) / "hlo"
        hdir.mkdir(parents=True, exist_ok=True)
        suffix0 = f"__{tag}" if tag else ""
        with gzip.open(
            hdir / f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}{suffix0}.txt.gz",
            "wt",
        ) as fh:
            fh.write(hlo)
    cost = analyze_hlo(hlo, n_devices)
    record["analyze_s"] = round(time.time() - t2, 2)
    record["hlo_cost"] = {
        "flops_per_device": cost.flops,
        "bytes_raw_per_device": cost.bytes_raw,
        "bytes_bf16_per_device": cost.bytes_bf16,
        "collective_traffic_raw": cost.collective_traffic_raw,
        "collective_traffic_bf16": cost.collective_traffic_bf16,
        "collective_ops": cost.collective_ops,
        "collective_counts": cost.collective_counts,
    }
    record["roofline"] = roofline_terms(
        cost.flops, cost.bytes_bf16, cost.collective_traffic_bf16
    )
    mf = model_flops(get_config(arch), model, shape, zo=(shape.kind == "train"))
    record["model_flops"] = mf
    record["useful_flops_fraction"] = (
        mf["model_flops_step"] / n_devices / max(cost.flops, 1e-30)
    )

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fname = out / f"{arch}__{shape_name}__{record['mesh']}{suffix}.json"
    fname.write_text(json.dumps(record, indent=1))
    if verbose:
        r = record["roofline"]
        print(
            f"[dryrun] {arch:18s} {shape_name:12s} {record['mesh']:6s} "
            f"compile={record['compile_s']:7.1f}s "
            f"compute={r['compute_s']:.3e}s mem={r['memory_s']:.3e}s "
            f"coll={r['collective_s']:.3e}s dom={r['dominant']:12s} "
            f"roofline_frac={r['roofline_fraction']:.3f}",
            flush=True,
        )
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--method", default="tezo_adam")
    ap.add_argument(
        "--kernel-mode", default="auto",
        choices=["auto", "pallas", "xla", "both"],
        help="hot-path lowering for every cell — the ZO leaf ops (all nine "
        "methods) and the forward compute (flash attention / selective "
        "scan) dispatch on it; 'both' runs each cell twice, "
        "tagging records [TAG-]kernel-xla / [TAG-]kernel-pallas so "
        "`benchmarks.roofline --tag [TAG-]kernel-xla --compare "
        "[TAG-]kernel-pallas` reports the two paths from this one "
        "invocation (the exact command is printed at the end)",
    )
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument(
        "--weight-quant", default="none",
        choices=["none", "nf4", "lut3", "lut4"],
        help="train cells quantize transformer block weights into packed "
        "QuantLeaf storage (3/4-bit LUT codes; in-tile dequant forward, "
        "τ-space perturb/update) before lowering the ZO step",
    )
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--continue-on-error", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument(
        "--preset", default="baseline", choices=["baseline", "optimized"],
        help="optimized = the §Perf recipes: kernel-modeled flash attention, "
        "chunked CE, pure-FSDP batch mapping (train cells), chunkwise mLSTM, "
        "shard_map EP MoE",
    )
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if args.all:
        cells = runnable_cells()
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    def preset_overrides(arch: str, shape: str) -> dict:
        if args.preset != "optimized":
            return {}
        cfg = get_config(arch)
        ov: dict = {"logits_chunk": 1024}
        if args.kernel_mode == "auto":
            # the preset's default lowering is the kernel path — but an
            # explicit --kernel-mode (incl. "both", whose whole point is the
            # per-leg comparison) must keep control of the dispatch knob
            ov["kernel_mode"] = "pallas"
        if cfg.family == "moe":
            ov["moe_impl"] = "ep"
        if cfg.family == "ssm":
            ov["mlstm_chunk"] = 256
        if shape == "train_4k" and cfg.family != "moe":
            ov["batch_axis_names"] = ("data", "model")
        return ov

    if args.kernel_mode == "both" and args.method not in KERNEL_METHODS:
        # even a hypothetical kernel-less ZO method still dispatches its
        # FORWARD compute on kernel_mode, so 'both' stays meaningful
        print(
            f"[dryrun] note: method {args.method!r} has no ZO kernel path; "
            "--kernel-mode both still compares the forward lowerings",
            flush=True,
        )
    if args.kernel_mode == "both":
        # one invocation → two tagged record sets for benchmarks.roofline
        prefix = args.tag + "-" if args.tag else ""
        kernel_runs = [
            ("xla", prefix + "kernel-xla"),
            ("pallas", prefix + "kernel-pallas"),
        ]
    else:
        kernel_runs = [(args.kernel_mode, args.tag)]

    failures = []
    n_cells = 0
    for arch, shape in cells:
        # kernel_mode now reaches the whole step: train cells dispatch the
        # ZO leaf ops AND the forward; prefill/decode cells dispatch their
        # forward, so they run per kernel mode too.
        runs = kernel_runs
        for mp in meshes:
            for kmode, tag in runs:
                try:
                    run_cell(
                        arch, shape, mp,
                        method=args.method, rank=args.rank,
                        out_dir=args.out, tag=tag, save_hlo=args.save_hlo,
                        overrides=preset_overrides(arch, shape),
                        kernel_mode=kmode,
                        weight_quant=args.weight_quant,
                    )
                    n_cells += 1
                    jax.clear_caches()
                except Exception as e:
                    failures.append((arch, shape, mp, kmode, repr(e)))
                    print(
                        f"[dryrun] FAIL {arch} {shape} mp={mp} kernel={kmode}: {e}",
                        flush=True,
                    )
                    traceback.print_exc()
                    if not args.continue_on_error:
                        raise
    if failures:
        print(f"[dryrun] {len(failures)} failures")
        raise SystemExit(1)
    print(f"[dryrun] all {n_cells} cells OK")
    if len(kernel_runs) == 2:
        mesh_hint = "multi" if args.mesh == "multi" else "single"
        print(
            "[dryrun] compare the two lowerings with: "
            f"python -m benchmarks.roofline --dir {args.out} "
            f"--mesh {mesh_hint} "
            f"--tag {kernel_runs[0][1]} --compare {kernel_runs[1][1]}"
        )


if __name__ == "__main__":
    main()
