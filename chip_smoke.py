"""Smoke run of the ZO trainer and the paged serving engine on a TPU.

    python3 chip_smoke.py [--seed N]   # one chip
    python3 chip_smoke.py --chips 4    # a four-chip host: mesh phases only

Drives the main path once through the entry points a user calls —
``launch.train.train`` and ``launch.serve.ServeEngine`` — on opt-125m at
its published widths (12 layers, d_model 768, vocab 50272, bf16), with
random weights and synthetic data made from ``--seed``.

One chip runs these phases, each checked against its stated tolerance:

* ``noise``   — ``noise_perturb`` draws (a 2-D leaf with an int32-negative
  seed word, and a stacked leaf) against ``ref.counter_normal_ref``;
* ``kernels`` — flash attention, paged decode and paged verify against
  their XLA references at opt-125m head widths;
* ``train``   — (a) ``train(method="tezo_adam", kernel_mode="pallas")`` for
  a few steps: finite losses that agree with the ``kernel_mode="xla"`` run;
* ``train_lut4`` — (b) the same with ``weight_quant="lut4"``, which puts
  ``quant_matmul`` in the forward;
* ``serve``   — (c) ``ServeEngine`` after ``warmup()`` answers 8 requests:
  prefill logits of one prompt agree across the two lowerings, every
  greedy token of the pallas and the xla engine is the argmax of the xla
  prefill reference (teacher-forced) within the logit tolerance, and
  ``compile_count`` does not grow after warmup;
* ``spec``    — (d) the same requests with ``spec_decode=True`` give the
  token streams of (c), bitwise, with no compile after warmup.

``--chips 4`` runs only what exists across chips: the tezo_adam step on a
(data 2, model 2) mesh of the real chips against the one-chip run, and the
probe-parallel step (q = 4 on data 4) against the sequential chained step.

The script refuses to run anywhere the kernels would not be Mosaic on a
TPU: off a TPU, with the Pallas interpreter on, or when either dispatch
query reports another path it exits non-zero before any phase and prints
no result.  A phase that misses its tolerance raises; nothing is caught.
Earlier lines print each phase's result and the seconds spent compiling;
the last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ARCH = "opt-125m"
STEPS = 4
# Relative loss agreement between two runs of the same few steps.  The
# lowerings (or layouts) round bf16 weights and activations at different
# points; over 8×128 tokens that moves the mean loss by far less than this.
LOSS_RTOL = 2e-3
# Absolute agreement of bf16 logits whose scale is ~1 (random init): about
# six bf16 ulps at |logit| ≈ 4.
LOGIT_ATOL = 0.1
# Transcendental ulps (log, cos, sqrt) between Mosaic and XLA, for z ~ N(0,1).
NOISE_ATOL = 1e-4
# bf16 attention outputs against the XLA reference: |Δ| ≤ atol + rtol·|ref|,
# rtol = two bf16 ulps.
ATTN_ATOL, ATTN_RTOL = 1e-2, 2.0**-6
N_REQUESTS = 8
MAX_NEW = 16


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: {msg}")


def _check(ok: bool, msg: str):
    if not ok:
        _fail(msg)


class CompileClock:
    """Seconds XLA spends compiling, read per phase."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def _report(clock: CompileClock, name: str, t0: float, c0: float, **result):
    line = {
        "phase": name,
        "ok": True,
        "compile_s": round(clock.total - c0, 3),
        "wall_s": round(time.perf_counter() - t0, 3),
        **result,
    }
    print(json.dumps(line), flush=True)


def preflight(chips: int):
    """The device and the dispatch must be the real thing, or no phase runs."""
    import jax

    from repro.core.dispatch import forward_execution, kernel_execution
    from repro.kernels import ops

    devices = jax.devices()
    dev = devices[0]
    _check(dev.platform == "tpu", f"platform is {dev.platform!r}, not 'tpu'")
    _check(len(devices) == chips, f"{len(devices)} devices, expected {chips}")
    _check(not ops.is_interpret(), "Pallas kernels would run in interpret mode")
    got = kernel_execution("tezo_adam", "pallas")
    _check(got == ("pallas", False), f"kernel_execution reports {got}")
    got = forward_execution("pallas")
    _check(got == ("pallas", True), f"forward_execution reports {got}")
    return dev, len(devices)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_noise(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    # the first word has its high bit set: as the int32 the kernel reads
    # from SMEM it is negative, so this checks the scalar int32 -> uint32
    # conversion keeps the bits
    key = jnp.array([0x9E3779B9, seed & 0xFFFFFFFF], jnp.uint32)
    worst = 0.0
    for shape, probe in (((768, 3072), 0), ((768, 3072), 5), ((50272, 768), 1)):
        w = jnp.zeros(shape, jnp.float32)
        got = jax.jit(
            lambda w, k, p=probe: ops.noise_perturb(w, k, 1.0, probe=p)
        )(w, key)
        want = jax.jit(
            lambda k, s=shape, p=probe: ref.counter_normal_ref(s, k, p)
        )(key)
        worst = max(worst, float(jnp.max(jnp.abs(got - want))))
    # a stacked leaf: the per-slice seeds ride a batched SMEM block
    stack = jnp.zeros((3, 768, 3072), jnp.float32)
    got = jax.jit(lambda w, k: ops.noise_perturb(w, k, 1.0, probe=2))(stack, key)
    seeds = ops._batch_seeds(key, 3)
    for i in range(3):
        want = ref.counter_normal_ref((768, 3072), seeds[i], 2)
        worst = max(worst, float(jnp.max(jnp.abs(got[i] - want))))
    z = np.asarray(got[0])
    _check(np.isfinite(z).all(), "non-finite noise")
    _check(worst <= NOISE_ATOL, f"noise vs counter_normal_ref: {worst} > {NOISE_ATOL}")
    return {"max_abs_diff": worst, "atol": NOISE_ATOL, "z_std": float(z.std())}


def phase_kernels(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models import layers

    H, dh, bf = 12, 64, jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (2, 256, H, dh), bf) for i in range(3))
    diffs = {}

    def diff(got, want):
        want = want.astype(jnp.float32)
        return got.astype(jnp.float32) - want, jnp.abs(want)

    diffs["flash_attention"] = diff(
        jax.jit(ops.flash_attention)(q, k, v), jax.jit(layers.full_attention)(q, k, v)
    )

    S, P, page, n_pool, T = 4, 4, 16, 17, 5
    kp = jax.random.normal(ks[3], (n_pool, page, H, dh), bf)
    vp = jax.random.normal(ks[4], (n_pool, page, H, dh), bf)
    bt = jnp.asarray(1 + np.arange(S * P).reshape(S, P), jnp.int32)
    lengths = jnp.asarray([1, 17, 40, 64 - T + 1], jnp.int32)
    qd = jax.random.normal(ks[5], (S, H, dh), bf)
    diffs["paged_decode_attention"] = diff(
        jax.jit(ops.paged_decode_attention)(qd, kp, vp, bt, lengths),
        jax.jit(layers.paged_decode_attention_ref)(qd, kp, vp, bt, lengths),
    )
    qv = jax.random.normal(ks[5], (S, T, H, dh), bf)
    diffs["paged_verify_attention"] = diff(
        jax.jit(ops.paged_verify_attention)(qv, kp, vp, bt, lengths),
        jax.jit(layers.paged_verify_attention_ref)(qv, kp, vp, bt, lengths),
    )
    out = {}
    for name, (d, ref_abs) in diffs.items():
        excess = float(jnp.max(jnp.abs(d) - ATTN_RTOL * ref_abs))
        _check(np.isfinite(excess), f"{name}: non-finite output")
        _check(excess <= ATTN_ATOL, f"{name} vs XLA reference: off by {excess}")
        out[name] = float(jnp.max(jnp.abs(d)))
    return {"max_abs_diff": out, "atol": ATTN_ATOL, "rtol": ATTN_RTOL}


def _losses(result: dict) -> list[float]:
    return [rec["loss"] for rec in result["history"]] + [result["final_eval_loss"]]


def _compare_losses(name: str, got: dict, want: dict) -> dict:
    import numpy as np

    a, b = np.asarray(_losses(got)), np.asarray(_losses(want))
    _check(np.isfinite(a).all() and np.isfinite(b).all(), f"{name}: non-finite loss")
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    _check(rel <= LOSS_RTOL, f"{name}: losses {a} vs {b}, rel {rel} > {LOSS_RTOL}")
    return {
        "losses": a.tolist(),
        "reference_losses": b.tolist(),
        "max_rel_diff": rel,
        "rtol": LOSS_RTOL,
        "bitwise": bool((a == b).all()),
    }


def _train(seed: int, **kw) -> dict:
    from repro.launch.train import train

    return train(
        arch=ARCH, method="tezo_adam", steps=STEPS, log_every=1,
        eval_every=10**9, seed=seed, verbose=False, **kw,
    )


def phase_train(seed: int, weight_quant: str) -> dict:
    got = _train(seed, kernel_mode="pallas", weight_quant=weight_quant)
    _check(
        (got["kernel_mode"], got["kernel_interpret"]) == ("pallas", False),
        f"train ran {got['kernel_mode']} interpret={got['kernel_interpret']}",
    )
    want = _train(seed, kernel_mode="xla", weight_quant=weight_quant)
    return _compare_losses(f"train[{weight_quant}]", got, want)


def _requests(seed: int, vocab: int):
    from repro.data import DataConfig, batch_at_step
    from repro.launch.serve import Request

    data = DataConfig(seq_len=48, global_batch=N_REQUESTS, vocab_size=vocab, seed=seed)
    toks = batch_at_step(data, 0)["tokens"]
    return [
        Request(id=f"r{i}", tokens=toks[i, : 8 + 5 * i], max_new=MAX_NEW, seed=i)
        for i in range(N_REQUESTS)
    ]


def _serve(cfg, params, requests, *, spec: bool):
    from repro.launch.serve import ServeEngine

    engine = ServeEngine(
        cfg, params, max_concurrent_decodes=4, max_prompt_len=64,
        max_new_tokens=MAX_NEW, page_size=16, spec_decode=spec,
    )
    engine.warmup()
    warm = engine.compile_count
    results, stats = engine.serve(requests, step_clock=True)
    _check(
        engine.compile_count == warm,
        f"compile_count grew after warmup: {warm} -> {engine.compile_count}",
    )
    return {r.id: results[r.id]["tokens"] for r in requests}, stats


def _greedy_margin(prefill, params, requests, streams) -> float:
    """Largest gap, over every emitted token, between the reference's best
    logit and the logit of the token the engine chose (teacher-forced)."""
    import numpy as np

    worst = 0.0
    for r in requests:
        hist = list(np.asarray(r.tokens))
        for tok in streams[r.id]:
            padded = np.zeros((1, 64), np.int32)
            padded[0, : len(hist)] = hist
            logits = np.asarray(prefill(params, padded, np.int32(len(hist))))[0]
            logits = logits.astype(np.float32)
            worst = max(worst, float(logits.max() - logits[int(tok)]))
            hist.append(int(tok))
    return worst


def phase_serve(seed: int):
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model

    base = get_config(ARCH)
    params = build_model(base).init(jax.random.PRNGKey(seed))
    requests = _requests(seed, base.vocab_size)
    cfg_p = base.reduced(kernel_mode="pallas")
    cfg_x = base.reduced(kernel_mode="xla")
    prefill_p = jax.jit(build_model(cfg_p).prefill_paged)
    prefill_x = jax.jit(build_model(cfg_x).prefill_paged)

    r0 = requests[-1]
    padded = np.zeros((1, 64), np.int32)
    padded[0, : len(r0.tokens)] = r0.tokens
    n0 = np.int32(len(r0.tokens))
    lp = np.asarray(prefill_p(params, padded, n0)[0], np.float32)
    lx = np.asarray(prefill_x(params, padded, n0)[0], np.float32)
    _check(np.isfinite(lp).all(), "non-finite prefill logits")
    logit_diff = float(np.max(np.abs(lp - lx)))
    _check(logit_diff <= LOGIT_ATOL, f"prefill logits: {logit_diff} > {LOGIT_ATOL}")

    streams_p, stats = _serve(cfg_p, params, requests, spec=False)
    streams_x, _ = _serve(cfg_x, params, requests, spec=False)
    margins = {}
    for name, streams in (("pallas", streams_p), ("xla", streams_x)):
        _check(
            all(len(s) == MAX_NEW for s in streams.values()),
            f"{name} engine emitted short streams",
        )
        margins[name] = _greedy_margin(
            lambda p, t, n: prefill_x(p, t, n)[0], params, requests, streams
        )
        _check(
            margins[name] <= LOGIT_ATOL,
            f"{name} greedy token off the reference argmax by {margins[name]}",
        )
    same = sum(bool((streams_p[k] == streams_x[k]).all()) for k in streams_p)
    result = {
        "prefill_logit_max_abs_diff": logit_diff,
        "greedy_margin": margins,
        "atol": LOGIT_ATOL,
        "streams_equal_across_lowerings": f"{same}/{len(requests)}",
        "emitted_tokens": stats["emitted_tokens"],
        "compile_count": stats["compile_count"],
    }
    return result, (cfg_p, params, requests, streams_p)


def phase_spec(cfg, params, requests, plain_streams) -> dict:
    streams, stats = _serve(cfg, params, requests, spec=True)
    for rid, s in plain_streams.items():
        _check(
            streams[rid].shape == s.shape and bool((streams[rid] == s).all()),
            f"spec stream of {rid} differs from the plain engine's",
        )
    return {
        "streams_equal_to_plain": f"{len(streams)}/{len(streams)}",
        "acceptance_rate": stats["acceptance_rate"],
        "tok_per_verify": stats["tok_per_verify"],
        "compile_count": stats["compile_count"],
    }


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------


def phase_mesh(seed: int) -> dict:
    from repro.launch.mesh import make_host_mesh

    one = _train(seed, kernel_mode="pallas")
    got = _train(seed, kernel_mode="pallas", mesh=make_host_mesh(data=2, model=2))
    _check(got["param_devices"] == 4, f"params span {got['param_devices']} devices")
    _check(got["sharded_param_leaves"] > 0, "no param leaf is split across chips")
    out = _compare_losses("mesh 2x2 vs one chip", got, one)
    out["sharded_param_leaves"] = got["sharded_param_leaves"]
    return out


def phase_probe_parallel(seed: int) -> dict:
    from repro.launch.mesh import make_host_mesh

    seq = _train(seed, kernel_mode="pallas", q_probes=4)
    got = _train(
        seed, kernel_mode="pallas", q_probes=4, probe_parallel=True,
        mesh=make_host_mesh(data=4, model=1),
    )
    _check(got["probe_lanes"] == 4, f"probe lanes {got['probe_lanes']}")
    _check(got["param_devices"] == 4, f"params span {got['param_devices']} devices")
    return _compare_losses("probe-parallel vs sequential", got, seq)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "launch" / "train.py").is_file():
        _fail(f"no repro package at {src}: run from a checkout of the repo")
    sys.path.insert(0, str(src))

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    dev, count = preflight(args.chips)
    clock = CompileClock()

    def run(name, fn, *a):
        t0, c0 = time.perf_counter(), clock.total
        out = fn(*a)
        res = out[0] if isinstance(out, tuple) else out
        _report(clock, name, t0, c0, **res)
        return out

    if args.chips == 4:
        run("mesh_2x2", phase_mesh, args.seed)
        run("probe_parallel_4x1", phase_probe_parallel, args.seed)
    else:
        run("noise", phase_noise, args.seed)
        run("kernels", phase_kernels, args.seed)
        run("train", phase_train, args.seed, "none")
        run("train_lut4", phase_train, args.seed, "lut4")
        _, served = run("serve", phase_serve, args.seed)
        run("spec", phase_spec, *served)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": count}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
