"""Driver of the ZO fine-tuning cells: a closed loop of the program's
jitted, donated TeZO step (``core.zo_step.build_zo_train_step``, built as
``launch/train.py`` builds it on one device).

Set-up makes the weights and the method's state in one jitted call from
the seed, compiles the step, and drives it through its first
``CHECK_STEPS`` steps on distinct batches: the same object and call that
the window then drives.  Those first steps are the ones the reference
follows.  The window dispatches steps back to back, keeping at most two
in flight, and ends with ``block_until_ready``.

Correctness compares, with the plain reference of ``bench/reference``:

* ``loss_gap``: each of the first steps' loss (the mean of f+ and f-),
  as the largest relative gap;
* ``grad_gap``: per leaf, the norm of the gradient estimate the
  optimizer took in (its first moment after one step, over 1 - beta1),
  the worst leaf's gap against max(its reference norm, the median
  leaf's);
* ``change_gap``: per leaf, ||W - W0|| after the first steps, the same
  way, leaving out leaves whose reference gradient is under a thousandth
  of the median leaf's.
"""
from __future__ import annotations

import gc
import statistics
import time
from collections import deque

import numpy as np

CHECK_STEPS = 3


def _imports():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def model_config(cell):
    from repro.configs.base import ModelConfig

    return ModelConfig(name=cell.config["name"],
                       kernel_mode=cell.traffic["kernel_mode"],
                       **cell.model)


def zo_dict(traffic: dict) -> dict:
    keys = ("method", "kernel_mode", "q_probes", "rank", "lr", "rho",
            "beta1", "beta2", "eps")
    return {k: traffic[k] for k in keys}


def zo_seed(seed: int) -> int:
    from bench.weights import seed31

    return seed31(seed, "zo")


def make_batches(m: dict, traffic: dict, seed: int) -> list:
    """``distinct_batches`` batches of [batch, seq] tokens, uniform over
    the vocabulary, from the seed; targets are the next tokens."""
    from bench.weights import seed31

    rng = np.random.default_rng(seed31(seed, "traffic"))
    K, B, S = traffic["distinct_batches"], traffic["batch"], traffic["seq"]
    toks = rng.integers(0, m["vocab_size"], size=(K, B, S + 1),
                        dtype=np.int32)
    return [{"tokens": t[:, :-1], "targets": t[:, 1:]} for t in toks]


class Program:
    """The system under test, built for one cell.  ``fault`` plants a
    defect under the timed path (for the checks of the comparison):
    "unchanged" returns the state it was given, "half_batch" takes the
    loss over the first half of each batch only."""

    def __init__(self, cell, fault: str | None = None):
        jax, jnp = _imports()
        from repro.core import ZOConfig, build_zo_train_step
        from repro.core.estimator import get_method
        from repro.core.zo_step import ZOTrainState
        from repro.models import build_model

        from bench import weights as W

        self.cell = cell
        self.m = cell.model
        t = cell.traffic
        self.model = build_model(model_config(cell))
        want = self.model.abstract_params()
        got = jax.eval_shape(lambda k: W.make_params(self.m, k),
                             jax.random.PRNGKey(0))
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))
        ):
            raise ValueError("bench/weights.py does not make the tree the "
                             "program's model declares")
        # the seed reaches the step only through its state (see init_fn)
        self.zo_cfg = ZOConfig(
            method=t["method"], kernel_mode=t["kernel_mode"], lr=t["lr"],
            rho=t["rho"], rank=t["rank"], q_probes=t["q_probes"],
            beta1=t["beta1"], beta2=t["beta2"], eps=t["eps"],
        )
        loss_fn = self.model.loss_fn
        if fault == "half_batch":
            full = loss_fn

            def loss_fn(p, b):
                return full(p, {k: v[: v.shape[0] // 2] for k, v in b.items()})
        elif fault not in (None, "unchanged"):
            raise ValueError(f"unknown fault {fault!r}")
        step = build_zo_train_step(loss_fn, self.zo_cfg)
        if fault == "unchanged":
            inner = step

            def step(state, batch):
                return state, inner(state, batch)[1]

        self.step = jax.jit(step, donate_argnums=0)
        self._change = {}

        # core.zo_step.init_zo_state for dense leaves, with the seed's key
        # an argument instead of the static ZOConfig.seed: one compiled
        # set-up call then serves every seed
        cfg = self.zo_cfg
        method = get_method(cfg.method)

        def init(wkey, key):
            params = W.make_params(self.m, wkey)
            mstate = method.init(params, jax.random.fold_in(key, 0xF0), cfg)
            return ZOTrainState(
                params=params, mstate=mstate,
                step=jnp.zeros((), jnp.int32),
                base_key=jax.random.fold_in(key, 0x5EED))

        self.init_fn = jax.jit(init)

    def init(self, seed: int):
        """Weights and method state from the seed, in one jitted call."""
        jax, _ = _imports()
        from bench import weights as W

        return self.init_fn(W.weights_key(seed),
                            jax.random.PRNGKey(zo_seed(seed)))

    def change_norms(self, params, seed: int) -> dict:
        """Per leaf ||W - W0||, W0 regenerated layer by layer."""
        jax, jnp = _imports()
        from repro.utils.tree import map_with_path

        from bench import weights as W

        m = self.m
        key = W.weights_key(seed)
        out = {}

        def one(path, w):
            name = path.split("'")[-2]
            if path.startswith("['blocks']"):
                if name not in self._change:
                    def f(w, k, name=name):
                        def body(l, acc):
                            d = (w[l].astype(jnp.float32) - W.layer_leaf(
                                m, k, l, name).astype(jnp.float32))
                            return acc + jnp.sum(d * d)

                        return jnp.sqrt(jax.lax.fori_loop(
                            0, w.shape[0], body, jnp.zeros((), jnp.float32)))

                    self._change[name] = jax.jit(f)
            elif name not in self._change:
                def f(w, k, name=name):
                    d = (w.astype(jnp.float32)
                         - W.outer_params(m, k)[name].astype(jnp.float32))
                    return jnp.sqrt(jnp.sum(d * d))

                self._change[name] = jax.jit(f)
            out[path] = self._change[name](w, key)
            return w

        map_with_path(one, params)
        return {p: float(v) for p, v in out.items()}


def grad_norms(mstate, beta1: float) -> dict:
    """Per leaf, the first moment's norm over (1 - beta1)."""
    out = {}
    for group in ("tau_m", "dense_m"):
        for p, a in mstate.get(group, {}).items():
            out[p] = float(np.linalg.norm(np.asarray(a, np.float64).ravel())
                           ) / (1 - beta1)
    return out


def first_steps(prog: Program, state, batches, seed: int):
    """Drive the step through the checked steps; returns the state, the
    readings the comparison takes, and the seconds spent reading the
    parameter change (which is the comparison's, not set-up)."""
    jax, _ = _imports()
    losses, grads = [], None
    for s in range(CHECK_STEPS):
        state, met = prog.step(state, batches[s])
        losses.append(float(met["loss"]))
        if s == 0:
            grads = grad_norms(jax.device_get(state.mstate),
                               prog.cell.traffic["beta1"])
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    changes = prog.change_norms(state.params, seed)
    check_s = time.perf_counter() - t0
    return state, {"losses": losses, "grad_norms": grads,
                   "change_norms": changes}, check_s


def reference_readings(cell, seed: int, host_batches, precision="f32"):
    from bench import weights as W
    from bench.reference import tezo

    return tezo.run(cell.model, zo_dict(cell.traffic), W.weights_key(seed),
                    zo_seed(seed), host_batches[:CHECK_STEPS], precision)


def _gap(got: float, want: float, floor: float) -> float:
    return abs(got - want) / max(abs(want), floor, 1e-30)


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Per leaf, the relative gaps of the gradient and change norms (for
    finding which leaf a reading comes from)."""
    rg, rc = ref["grad_norms"], ref["change_norms"]
    med_g = statistics.median(rg.values())
    med_c = statistics.median(rc.values())
    return {p: [_gap(prog["grad_norms"].get(p, 0.0), rg[p], med_g),
                _gap(prog["change_norms"].get(p, 0.0), rc[p], med_c),
                prog["change_norms"].get(p, 0.0), rc[p]] for p in rg}


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """The numbers compared, each beside its limit."""
    loss_gap = max(_gap(p, r, 0.0)
                   for p, r in zip(prog["losses"], ref["losses"]))
    rg = ref["grad_norms"]
    med_g = statistics.median(rg.values())
    grad_gap = max(_gap(prog["grad_norms"].get(p, 0.0), r, med_g)
                   for p, r in rg.items())
    moved = [p for p, r in rg.items() if r >= 1e-3 * med_g]
    rc = ref["change_norms"]
    med_c = statistics.median(rc[p] for p in moved)
    change_gap = max(_gap(prog["change_norms"].get(p, 0.0), rc[p], med_c)
                     for p in moved)
    values = {"loss_gap": loss_gap, "grad_gap": grad_gap,
              "change_gap": change_gap}
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def readings(cell, seed: int, mode: str = "program", prog=None):
    """(program-side readings, reference readings) for one seed, with no
    measured window.  ``mode`` "control" puts the fp8 reference in the
    program's place; "unchanged" and "half_batch" plant those faults."""
    batches = make_batches(cell.model, cell.traffic, seed)
    if mode == "control":
        got = reference_readings(cell, seed, batches, "fp8")
    else:
        jax, _ = _imports()
        prog = prog or Program(cell, None if mode == "program" else mode)
        state = prog.init(seed)
        dev = [jax.device_put(b) for b in batches[:CHECK_STEPS]]
        state, got, _ = first_steps(prog, state, dev, seed)
        del state, dev
        gc.collect()
    return got, reference_readings(cell, seed, batches)


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        fault: str | None = None) -> dict:
    jax, _ = _imports()
    from bench import harness

    clock = harness.CompileClock()
    m, t = cell.model, cell.traffic
    prog = Program(cell, fault)
    host_batches = make_batches(m, t, seed)
    batches = [jax.device_put(b) for b in host_batches]
    state = jax.block_until_ready(prog.init(seed))
    t_init = time.perf_counter()
    state, got, check_s = first_steps(prog, state, batches, seed)
    setup_s = time.perf_counter() - t_start - check_s
    parts = {"compile_s": clock.total, "to_weights_s": t_init - t_start,
             "first_steps_s": time.perf_counter() - t_init - check_s,
             "check_s": check_s}

    def window():
        nonlocal state
        n, pending, losses = 0, deque(), []
        K = len(batches)
        t0 = time.perf_counter()
        while True:
            with jax.profiler.StepTraceAnnotation("zo.step", step_num=n):
                state, met = prog.step(state, batches[(CHECK_STEPS + n) % K])
            n += 1
            losses.append(met["loss"])
            pending.append(met["loss"])
            if len(pending) > 2:
                with jax.profiler.TraceAnnotation("zo.wait"):
                    pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("zo.drain"):
            jax.block_until_ready(state)
        return n, time.perf_counter() - t0, losses

    compiles_before = clock.count
    if trace:
        with harness.traced_window() as tw:
            n, win_s, losses = window()
    else:
        n, win_s, losses = window()
    compiled_in_window = clock.count - compiles_before
    loss_vals = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(loss_vals)))
    device = harness.device_record(cell.chips)

    per_layer = breakdown = None
    if trace:
        from bench import cell as cells
        from bench import counts
        from bench import trace as TR

        red = TR.reduce(TR.load(tw["xplane"])) if tw.get("xplane") else \
            TR.reduce(TR.Trace())
        harness.drop_trace(tw)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {
            "kind": "zo", "steps": n, "host_window_s": win_s,
            "busy_s": red["busy_s"], "window_s": red["window_s"],
            "ops": red["ops"], "modules": red["modules"], "model": m, "traffic": t,
            "peak": harness.peaks(device["kind"]), "counts": counts,
        }
        per_layer = cells.read_per_layer(cell, ctx)
        breakdown = red["breakdown"]

    # the program's state goes before the reference runs
    del state, batches
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(cell, seed, host_batches)
    parts["reference_s"] = time.perf_counter() - t_ref
    checks = compare(got, ref, cell.limits)
    checks["compiles_in_window"] = {"value": compiled_in_window, "limit": 0}
    correct = failed == 0 and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())

    if trace:
        metrics = per_layer
    else:
        B, S = t["batch"], t["seq"]
        metrics = {
            "train_tokens_per_s": {"value": B * S * n / win_s,
                                   "unit": "tokens/s"},
            "train_peak_hbm_gib": {
                "value": device["memory_peak_bytes"] / 2**30, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {k: v for k, v in metrics.items()
                   if k in {e["name"] for e in cell.end_to_end}}
    out = {"correct": bool(correct), "attempted": n, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["setup_parts"] = parts
    out["checks"] = checks
    return out
