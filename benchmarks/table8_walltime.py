"""Paper Table 8 / Fig 3b: wall-clock time per iteration, per ZO method.

CPU analogue of the paper's H100 table: per-step time of the jitted ZO step
on the opt-125m smoke model at two widths.  The paper's qualitative claims to
check: low-rank methods ≈ MeZO speed (small models may be slightly slower);
TeZO-Adam ≪ MeZO-Adam because moments live in τ-space.

Kernel dispatch: every method is timed on BOTH hot-path lowerings in the
same invocation — ``kernel_mode="xla"`` (dense reconstruct / dense
jax.random noise) and ``kernel_mode="pallas"`` (fused kernels: tile-resident
Z for TeZO/LOZO/SubZO, on-chip PRNG noise for MeZO) — so the comparison is
fused-vs-fused rather than a fused TeZO against unfused baselines.  On CPU
the pallas legs run in interpret mode, so those columns are a *semantics/
plumbing* check here and only a speed claim on TPU.

Sharded leg: the same method × kernel-mode sweep also runs on a 2×4
(data, model) host-platform mesh — 8 fake CPU devices in a subprocess, so
this process keeps seeing exactly one device — through the shard-aware
dispatch (shard_map'd local-shard kernels, see core.dispatch).  Those rows
are labeled ``mesh: "2x4-host"``; being host-platform multi-device on one
CPU they measure plumbing/compile sanity, not device-parallel speed.

Forward leg: the forward compute rides the same dispatch now (PR 4), so the
bench also times a PREFILL forward per model × kernel mode — opt-125m
(attention) and hymba (attention + selective-scan heads) smoke configs,
single-device plus a 2×4-host sharded row — with the analytic forward
bytes-moved model (``common.forward_bytes_model``: the score/state traffic
the flash-attention and selective-scan kernels remove).  Off-TPU the pallas
forward executes the marker-region XLA twin (``executed: "xla-region"``),
so those rows are dispatch/plumbing coverage; kernel speed is the on-TPU
follow-on, same as the ZO rows.

Besides the stdout CSV, ``run()`` writes ``results/BENCH_kernels.json`` —
per-(leg, model, method, kernel-mode, mesh) walltime plus an analytic
bytes-moved estimate — so the perf trajectory is machine-trackable across
PRs (``benchmarks/check_bench.py`` gates CI on record coverage, including
the forward-leg records).  Schema 5: every zo-step row records its step
schedule (``q_probes``, ``restore_mode``, ``probe_parallel``, ``zo_passes``
— 2q+1 full-W passes on the chained default; see
``repro.core.zo_step.zo_pass_count``) and the bytes-moved model is
pass-count-aware; a probe-parallel leg (``mesh: "2x4-host-pp"``, q=2 probes
split over the D=2 data lanes) additionally records ``per_replica_passes``
(2·ceil(q/D)+1 = 3 — the walltime-relevant per-replica traffic).
``check_bench`` fails a fresh file whose zo-step rows lack ``zo_passes``
or that has no probe-parallel row.

Serve leg (schema 6): the continuous-batching ``ServeEngine`` runs a seeded
Poisson arrival trace per kernel mode (``benchmarks.serving_latency``) and
records ``leg: "serve"`` rows — sustained ``tok_per_s``, TTFT p50/p99,
per-output-token latency p50/p99, ``max_concurrent_decodes`` — next to the
walltime rows.  Off-TPU the paged decode-attention kernel executes its
marker-region XLA twin, so CPU serve rows are latency-structure/plumbing
coverage like the forward leg's.  ``check_bench`` fails a fresh file with
no serve rows or serve rows missing the throughput/TTFT fields.

Speculative serve leg (schema 8): the same Poisson trace is served twice —
plain engine, then with ``spec_decode`` (prompt-lookup drafts scored by the
multi-token paged verify kernel) — and the spec rows record
``acceptance_rate``, ``tok_per_verify``, ``spec_tok_per_s`` against
``baseline_tok_per_s``, plus per-request ``queue_*`` percentiles now split
from TTFT on every serve row.  The greedy spec stream is asserted bitwise
identical to the baseline before a row is recorded.  ``check_bench``
(schema ≥ 8) fails a fresh file whose serve leg has no spec row or whose
spec rows lack ``acceptance_rate`` / ``spec_tok_per_s`` / ``draft_len``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import jax

from benchmarks.common import (
    emit_csv,
    forward_bytes_model,
    time_fn,
    zo_step_bytes_model,
)
from benchmarks.serving_latency import serve_leg_rows, spec_serve_leg_rows
from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.core import KERNEL_METHODS, ZOConfig, build_zo_train_step, init_zo_state
from repro.core import kernel_execution, zo_pass_count
from repro.core.dispatch import forward_execution
from repro.kernels.ops import is_interpret
from repro.models import build_model
from repro.utils.tree import tree_num_params

METHODS = [
    "mezo", "mezo_m", "mezo_adam", "lozo", "lozo_m", "subzo",
    "tezo", "tezo_m", "tezo_adam",
]

# The forward leg's models: a pure-attention transformer and the hybrid
# whose blocks exercise BOTH forward kernels (flash attention + the Mamba
# selective scan).
FORWARD_MODELS = ("opt-125m", "hymba-1.5b")
FORWARD_SHAPE = ShapeConfig("bench-fwd", seq_len=64, global_batch=4, kind="prefill")

BENCH_JSON = Path("results") / "BENCH_kernels.json"

# The sharded leg's mesh: (data, model) over 8 host-platform devices.
SHARDED_MESH = (2, 4)
SHARDED_MESH_LABEL = "2x4-host"
# The probe-parallel leg: same mesh, but the data axis holds PROBE replicas
# (cfg.probe_parallel) — q=2 probes over D=2 lanes, 2·ceil(q/D)+1 = 3
# per-replica passes instead of the sequential 5.
PP_MESH_LABEL = "2x4-host-pp"
PP_BENCH_METHODS = ("tezo_adam", "mezo")
PP_Q = 2
_CHILD_MARKER = "BENCH_SHARDED_JSON:"


def _hardware_label() -> str:
    """Schema-7 hardware tag: "cpu" / "gpu" / "tpu:<device_kind>".  Rows
    from different hardware are never walltime-comparable, so check_bench
    ratchets coverage per hardware value instead of globally."""
    d = jax.devices()[0]
    return f"tpu:{d.device_kind}" if d.platform == "tpu" else d.platform


def _kernel_label(method: str, kernel_mode: str) -> str:
    resolved, interp = kernel_execution(method, kernel_mode)
    return "pallas-interpret" if resolved == "pallas" and interp else resolved


def _forward_label(kernel_mode: str) -> tuple[str, str]:
    """(kernel label, executed detail) for a forward-leg record.

    The label keys the coverage ratchet; ``executed`` records what actually
    ran — "mosaic" (TPU kernel), "interpret" (forced emulation), or
    "xla-region" (the off-TPU marker-region twin, a plumbing row)."""
    path, kernel = forward_execution(kernel_mode)
    if path != "pallas":
        return "xla", "xla"
    if not kernel:
        return "pallas", "xla-region"
    return "pallas", "interpret" if is_interpret() else "mosaic"


def _forward_row(cfg, n_params: int, kernel_mode: str, mesh_label: str,
                 sec: float) -> dict:
    label, executed = _forward_label(kernel_mode)
    return {
        "leg": "forward",
        "model": cfg.name,
        "method": f"prefill:{cfg.name}",
        "kernel": label,
        "executed": executed,
        "mesh": mesh_label,
        "ms_per_iter": round(sec * 1e3, 2),
        "bytes_moved_est_mb": round(
            forward_bytes_model(
                cfg, n_params, FORWARD_SHAPE.global_batch,
                FORWARD_SHAPE.seq_len, label,
            ) / 2 ** 20,
            1,
        ),
    }


def forward_leg_rows(iters: int) -> list[dict]:
    """Prefill-forward walltime per model × kernel mode (single device)."""
    rows = []
    for arch in FORWARD_MODELS:
        base = get_smoke_config(arch)
        for kernel_mode in ("xla", "pallas"):
            cfg = base.reduced(kernel_mode=kernel_mode)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            n_params = tree_num_params(params)
            batch = model.make_inputs(jax.random.PRNGKey(1), FORWARD_SHAPE)
            prefill = jax.jit(
                lambda p, b, m=model: m.prefill(p, b, FORWARD_SHAPE.seq_len)
            )
            sec = time_fn(
                lambda p=params, b=batch: prefill(p, b)[0], iters=iters
            )
            rows.append(_forward_row(cfg, n_params, kernel_mode, "1x1", sec))
            jax.clear_caches()
    return rows


def _single_device_rows(widths, iters: int) -> list[dict]:
    rows = []
    shape = ShapeConfig("bench", seq_len=64, global_batch=4, kind="train")
    for width_mult in widths:
        cfg = get_smoke_config("opt-125m")
        cfg = cfg.reduced(
            d_model=cfg.d_model * width_mult,
            d_ff=cfg.d_ff * width_mult,
            head_dim=cfg.head_dim * width_mult,
        )
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        n_params = tree_num_params(params)
        batch = model.make_inputs(jax.random.PRNGKey(1), shape)
        base = None
        for method in METHODS:
            modes = ("xla", "pallas") if method in KERNEL_METHODS else ("xla",)
            for kernel_mode in modes:
                zo_cfg = ZOConfig(
                    method=method, kernel_mode=kernel_mode, rank=16,
                    lr=1e-5, lazy_interval=50,
                )
                state = init_zo_state(params, zo_cfg)
                step = jax.jit(build_zo_train_step(model.loss_fn, zo_cfg))
                sec = time_fn(
                    lambda s=state, b=batch: step(s, b)[1]["loss"], iters=iters
                )
                if method == "mezo" and kernel_mode == "xla":
                    base = sec
                resolved, _ = kernel_execution(method, kernel_mode)
                rows.append(
                    {
                        "leg": "zo-step",
                        "model": f"{cfg.name}-x{width_mult}",
                        "method": method,
                        "kernel": _kernel_label(method, kernel_mode),
                        "mesh": "1x1",
                        "ms_per_iter": round(sec * 1e3, 2),
                        "vs_mezo": round(sec / base, 3) if base else 1.0,
                        # schema 4: the step schedule is part of the record
                        # (2q+1 chained full-W passes — check_bench ratchets
                        # on the field's presence)
                        "q_probes": zo_cfg.q_probes,
                        "restore_mode": zo_cfg.restore_mode,
                        "probe_parallel": False,
                        "zo_passes": zo_pass_count(
                            zo_cfg.q_probes, zo_cfg.restore_mode
                        ),
                        "bytes_moved_est_mb": round(
                            zo_step_bytes_model(
                                n_params, method, resolved,
                                q_probes=zo_cfg.q_probes,
                                restore_mode=zo_cfg.restore_mode,
                            )
                            / 2 ** 20,
                            1,
                        ),
                    }
                )
    return rows


def _quant_storage_stats(params) -> tuple[int, int, int]:
    """(n_quant_elements, stored_bytes, dense_f16_bytes) over the QuantLeaf
    leaves of a quantized parameter tree.  The dense baseline is the paper's
    fp16 storage (2 B/element) regardless of the bench model's dtype, so the
    recorded ``weight_bytes_reduction`` is comparable across configs."""
    from repro.core import quant
    from repro.utils.tree import map_with_path

    stats = {"n": 0, "stored": 0, "dense": 0}

    def visit(path, leaf):
        if isinstance(leaf, quant.QuantLeaf):
            stats["n"] += leaf.size
            stats["stored"] += quant.stored_weight_bytes(leaf)
            stats["dense"] += leaf.size * 2
        return leaf

    map_with_path(visit, params)
    return stats["n"], stats["stored"], stats["dense"]


def quant_leg_rows(iters: int) -> list[dict]:
    """The quantized-leaf leg (schema 7): tezo / tezo_adam / mezo on lut4
    QuantLeaf weights, both lowerings, single device.

    Runs at 8× smoke width (d_model 512) so the per-channel codebooks
    amortize to a real packed-storage profile: the recorded
    ``weight_bytes_reduction`` (dense-f16 bytes ÷ stored packed bytes over
    the quantized leaves) must clear 3× for the TeZO rows — the number
    check_bench ratchets on.  The bytes-moved model drops the quantized
    elements from every TeZO-family ZO pass (perturb/update write the
    r-vector ``acc`` only); the MeZO row keeps full per-pass traffic (its
    dense ``nacc`` still round-trips) and is here for knob coverage, not a
    storage claim."""
    rows = []
    shape = ShapeConfig("bench", seq_len=64, global_batch=4, kind="train")
    width_mult = 8
    base = get_smoke_config("opt-125m")
    cfg = base.reduced(
        d_model=base.d_model * width_mult,
        d_ff=base.d_ff * width_mult,
        head_dim=base.head_dim * width_mult,
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = tree_num_params(params)
    batch = model.make_inputs(jax.random.PRNGKey(1), shape)
    for method in ("tezo", "tezo_adam", "mezo"):
        for kernel_mode in ("xla", "pallas"):
            zo_cfg = ZOConfig(
                method=method, kernel_mode=kernel_mode, rank=16,
                lr=1e-5, lazy_interval=50, weight_quant="lut4",
            )
            state = init_zo_state(params, zo_cfg)
            n_quant, stored, dense_f16 = _quant_storage_stats(state.params)
            step = jax.jit(build_zo_train_step(model.loss_fn, zo_cfg))
            sec = time_fn(
                lambda s=state, b=batch: step(s, b)[1]["loss"], iters=iters
            )
            resolved, _ = kernel_execution(method, kernel_mode)
            rows.append(
                {
                    "leg": "zo-step",
                    "model": f"{cfg.name}-x{width_mult}",
                    "method": method,
                    "kernel": _kernel_label(method, kernel_mode),
                    "mesh": "1x1",
                    "ms_per_iter": round(sec * 1e3, 2),
                    "q_probes": zo_cfg.q_probes,
                    "restore_mode": zo_cfg.restore_mode,
                    "probe_parallel": False,
                    "zo_passes": zo_pass_count(
                        zo_cfg.q_probes, zo_cfg.restore_mode
                    ),
                    "weight_quant": zo_cfg.weight_quant,
                    "quant_params": int(n_quant),
                    "weight_bytes_reduction": round(dense_f16 / stored, 2),
                    "bytes_moved_est_mb": round(
                        zo_step_bytes_model(
                            n_params, method, resolved,
                            q_probes=zo_cfg.q_probes,
                            restore_mode=zo_cfg.restore_mode,
                            weight_quant=zo_cfg.weight_quant,
                            n_quant_params=n_quant,
                        ) / 2 ** 20,
                        1,
                    ),
                }
            )
            jax.clear_caches()
    return rows


def sharded_leg_rows(iters: int) -> list[dict]:
    """Time every method × kernel-mode on the host-platform mesh.

    Must run in a process whose XLA_FLAGS forced ≥ 8 host devices BEFORE the
    first jax import — ``run()`` spawns it as a subprocess (below); call it
    directly only from such an environment.
    """
    # sharding-invariant jax.random so the dense-fallback leaves see the
    # same streams as the single-device rows (see core.dispatch docs)
    jax.config.update("jax_threefry_partitionable", True)
    from repro.distributed import (
        batch_shardings,
        param_spec_table,
        zo_state_shardings,
    )
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=SHARDED_MESH[0], model=SHARDED_MESH[1])
    shape = ShapeConfig("bench", seq_len=64, global_batch=4, kind="train")
    cfg = get_smoke_config("opt-125m").reduced(
        spmd_hints=True, batch_axis_names=("data",)
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = tree_num_params(params)
    batch = model.make_inputs(jax.random.PRNGKey(1), shape)
    b_sh = batch_shardings(mesh, jax.eval_shape(lambda: batch))
    rows = []
    base = None
    for method in METHODS:
        for kernel_mode in ("xla", "pallas"):
            zo_cfg = ZOConfig(
                method=method, kernel_mode=kernel_mode, rank=16,
                lr=1e-5, lazy_interval=50,
            )
            state = init_zo_state(params, zo_cfg)
            st_sh = zo_state_shardings(
                mesh, model.logical_axes(), jax.eval_shape(lambda: state)
            )
            step = jax.jit(
                build_zo_train_step(
                    model.loss_fn, zo_cfg, mesh=mesh,
                    param_specs=param_spec_table(st_sh.params),
                ),
                in_shardings=(st_sh, b_sh),
                out_shardings=(st_sh, None),
            )
            with mesh:
                state_d = jax.device_put(state, st_sh)
                batch_d = jax.device_put(batch, b_sh)
                sec = time_fn(
                    lambda s=state_d, b=batch_d: step(s, b)[1]["loss"],
                    iters=iters,
                )
            if method == "mezo" and kernel_mode == "xla":
                base = sec
            resolved, _ = kernel_execution(method, kernel_mode)
            rows.append(
                {
                    "leg": "zo-step",
                    "model": f"{cfg.name}-x1",
                    "method": method,
                    "kernel": _kernel_label(method, kernel_mode),
                    "mesh": SHARDED_MESH_LABEL,
                    "ms_per_iter": round(sec * 1e3, 2),
                    "vs_mezo": round(sec / base, 3) if base else 1.0,
                    "q_probes": zo_cfg.q_probes,
                    "restore_mode": zo_cfg.restore_mode,
                    "probe_parallel": False,
                    "zo_passes": zo_pass_count(
                        zo_cfg.q_probes, zo_cfg.restore_mode
                    ),
                    "bytes_moved_est_mb": round(
                        zo_step_bytes_model(
                            n_params, method, resolved,
                            q_probes=zo_cfg.q_probes,
                            restore_mode=zo_cfg.restore_mode,
                        ) / 2 ** 20,
                        1,
                    ),
                }
            )
            jax.clear_caches()
    return rows


def probe_parallel_rows(iters: int) -> list[dict]:
    """The probe-parallel leg (same subprocess contract as
    ``sharded_leg_rows``): ``cfg.probe_parallel`` on the 2×4 host mesh, so
    the D=2 data lanes each evaluate a disjoint slice of the q=2 probes and
    the busiest replica makes 2·ceil(q/D)+1 = 3 full-W passes instead of the
    sequential 2q+1 = 5.  State and batch are REPLICATED (the data axis
    holds probe replicas, not batch shards; ``param_specs={}``).  Rows are
    labeled ``mesh: "2x4-host-pp"`` and carry the schema-5 fields
    ``probe_parallel`` / ``per_replica_passes``; ``zo_passes`` records the
    per-replica count (the walltime-relevant number on this leg)."""
    jax.config.update("jax_threefry_partitionable", True)
    from repro.distributed import replicated_tree
    from repro.launch.mesh import make_host_mesh

    lanes = SHARDED_MESH[0]
    mesh = make_host_mesh(data=lanes, model=SHARDED_MESH[1])
    shape = ShapeConfig("bench", seq_len=64, global_batch=4, kind="train")
    cfg = get_smoke_config("opt-125m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = tree_num_params(params)
    batch = model.make_inputs(jax.random.PRNGKey(1), shape)
    b_sh = replicated_tree(mesh, jax.eval_shape(lambda: batch))
    rows = []
    for method in PP_BENCH_METHODS:
        for kernel_mode in ("xla", "pallas"):
            zo_cfg = ZOConfig(
                method=method, kernel_mode=kernel_mode, rank=16,
                lr=1e-5, lazy_interval=50, q_probes=PP_Q,
                probe_parallel=True,
            )
            state = init_zo_state(params, zo_cfg)
            st_sh = replicated_tree(mesh, jax.eval_shape(lambda: state))
            step = jax.jit(
                build_zo_train_step(
                    model.loss_fn, zo_cfg, mesh=mesh, param_specs={},
                ),
                in_shardings=(st_sh, b_sh),
                out_shardings=(st_sh, None),
            )
            with mesh:
                state_d = jax.device_put(state, st_sh)
                batch_d = jax.device_put(batch, b_sh)
                sec = time_fn(
                    lambda s=state_d, b=batch_d: step(s, b)[1]["loss"],
                    iters=iters,
                )
            resolved, _ = kernel_execution(method, kernel_mode)
            per_replica = zo_pass_count(
                PP_Q, zo_cfg.restore_mode, probe_lanes=lanes
            )
            rows.append(
                {
                    "leg": "zo-step",
                    "model": f"{cfg.name}-x1",
                    "method": method,
                    "kernel": _kernel_label(method, kernel_mode),
                    "mesh": PP_MESH_LABEL,
                    "ms_per_iter": round(sec * 1e3, 2),
                    "q_probes": PP_Q,
                    "restore_mode": zo_cfg.restore_mode,
                    "probe_parallel": True,
                    "probe_lanes": lanes,
                    "per_replica_passes": per_replica,
                    "zo_passes": per_replica,
                    "bytes_moved_est_mb": round(
                        zo_step_bytes_model(
                            n_params, method, resolved, q_probes=PP_Q,
                            restore_mode=zo_cfg.restore_mode,
                            probe_lanes=lanes,
                        ) / 2 ** 20,
                        1,
                    ),
                }
            )
            jax.clear_caches()
    return rows


def sharded_forward_rows(iters: int) -> list[dict]:
    """The forward leg on the 2×4 host mesh (same subprocess contract as
    ``sharded_leg_rows``): a batch-sharded prefill with the dispatch shard
    context registered, so on TPU the pallas rows time the shard_map'd
    kernels; on CPU they time the GSPMD-partitioned marker-region twin
    (plumbing/compile sanity, like every other host-mesh row)."""
    from repro.core import dispatch
    from repro.distributed import batch_shardings
    from repro.distributed.sharding import param_shardings
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=SHARDED_MESH[0], model=SHARDED_MESH[1])
    rows = []
    base = get_smoke_config("opt-125m").reduced(
        spmd_hints=True, batch_axis_names=("data",)
    )
    for kernel_mode in ("xla", "pallas"):
        cfg = base.reduced(kernel_mode=kernel_mode)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        n_params = tree_num_params(params)
        batch = model.make_inputs(jax.random.PRNGKey(1), FORWARD_SHAPE)
        p_sh = param_shardings(
            mesh, model.logical_axes(), model.abstract_params()
        )
        b_sh = batch_shardings(mesh, jax.eval_shape(lambda: batch))

        def prefill_fn(p, b, m=model):
            with dispatch.shard_context(mesh, {}):
                return m.prefill(p, b, FORWARD_SHAPE.seq_len)

        step = jax.jit(prefill_fn, in_shardings=(p_sh, b_sh))
        with mesh:
            p_d = jax.device_put(params, p_sh)
            b_d = jax.device_put(batch, b_sh)
            sec = time_fn(lambda: step(p_d, b_d)[0], iters=iters)
        rows.append(
            _forward_row(cfg, n_params, kernel_mode, SHARDED_MESH_LABEL, sec)
        )
        jax.clear_caches()
    return rows


def _sharded_leg_subprocess(iters: int) -> list[dict]:
    """Run the sharded leg in a child with 8 fake host devices (this process
    must keep seeing exactly one device).  The child is a CPU leg: on a
    TPU host this process already holds the chip, so the child is kept
    off it with JAX_PLATFORMS=cpu (XLA_FLAGS host devices alone do not)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    repo = Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(repo / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.table8_walltime",
         "--sharded-child", "--iters", str(iters)],
        env=env, cwd=repo, capture_output=True, text=True, timeout=3600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded bench leg failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith(_CHILD_MARKER):
            return json.loads(line[len(_CHILD_MARKER):])
    raise RuntimeError(f"sharded bench leg emitted no records:\n{proc.stdout[-2000:]}")


def run(
    out_json: Path | str = BENCH_JSON,
    widths=(1, 4),
    iters: int = 4,
    sharded: bool = True,
) -> list[dict]:
    rows = _single_device_rows(widths, iters)
    rows += quant_leg_rows(iters)
    rows += forward_leg_rows(iters)
    rows += serve_leg_rows()
    rows += spec_serve_leg_rows()
    if sharded:
        rows += _sharded_leg_subprocess(iters)
    # schema 7: every record is hardware-labeled — rows from different
    # hardware are never comparable, and check_bench ratchets coverage per
    # hardware value (the sharded child runs on this host, so one stamp
    # covers every leg)
    hw = _hardware_label()
    for r in rows:
        r.setdefault("hardware", hw)
    # the legs carry different columns — emit as separate CSV blocks
    # (probe-parallel zo-step rows have per_replica_passes instead of
    # vs_mezo, quantized rows carry weight_bytes_reduction)
    emit_csv(
        "table8_walltime",
        [r for r in rows
         if r["leg"] == "zo-step" and not r.get("probe_parallel")
         and r.get("weight_quant", "none") == "none"],
    )
    emit_csv(
        "table8_walltime_quant",
        [r for r in rows
         if r["leg"] == "zo-step" and r.get("weight_quant", "none") != "none"],
    )
    emit_csv(
        "table8_walltime_probe_parallel",
        [r for r in rows if r["leg"] == "zo-step" and r.get("probe_parallel")],
    )
    emit_csv(
        "table8_walltime_forward", [r for r in rows if r["leg"] == "forward"]
    )
    emit_csv(
        "table8_walltime_serve", [r for r in rows if r["leg"] == "serve"]
    )
    out = Path(out_json)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(
        json.dumps(
            {
                # schema 5: zo-step rows carry q_probes / restore_mode /
                # probe_parallel / zo_passes (the chained 2q+1 full-W pass
                # schedule, or the per-replica 2·ceil(q/D)+1 on the
                # probe-parallel leg, which also records per_replica_passes).
                # schema 6: serve-leg rows (continuous-batching engine under
                # Poisson arrival — tok_per_s, TTFT/TPOT percentiles,
                # max_concurrent_decodes)
                # schema 7: every record carries ``hardware`` ("cpu" /
                # "tpu:<kind>"; coverage ratchets per hardware value) and a
                # quantized zo-step leg (``weight_quant: "lut4"`` QuantLeaf
                # rows with ``weight_bytes_reduction`` — packed storage vs
                # dense f16 — and a packed-code-aware bytes-moved model)
                # schema 8: a speculative serve leg (``spec_decode: true``
                # rows with acceptance_rate / tok_per_verify / spec_tok_per_s
                # vs baseline_tok_per_s) and queue_* percentiles split from
                # TTFT on every serve row
                "schema": 8,
                "bench": "table8_walltime",
                # interpret-mode pallas rows are semantics checks, not
                # fused-kernel speed measurements — consumers must filter
                # (the per-row "kernel" label also marks them); mesh-labeled
                # rows are host-platform multi-device (plumbing, not speed)
                "interpret": bool(is_interpret()),
                "records": rows,
            },
            indent=1,
        )
    )
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(BENCH_JSON))
    ap.add_argument(
        "--widths", default="1,4",
        help="comma-separated opt-125m-smoke width multipliers (CI uses 1)",
    )
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument(
        "--no-sharded", action="store_true",
        help="skip the 2x4 host-platform mesh leg",
    )
    ap.add_argument(
        "--sharded-child", action="store_true", help=argparse.SUPPRESS
    )
    args = ap.parse_args()
    if args.sharded_child:
        rows = (
            sharded_leg_rows(args.iters)
            + probe_parallel_rows(args.iters)
            + sharded_forward_rows(args.iters)
        )
        print(_CHILD_MARKER + json.dumps(rows), flush=True)
        return
    widths = tuple(int(w) for w in str(args.widths).split(","))
    run(args.out, widths=widths, iters=args.iters, sharded=not args.no_sharded)


if __name__ == "__main__":
    main()
