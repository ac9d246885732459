"""Kernel-dispatch parity across ALL nine ZO methods.

Factor-carried methods (TeZO family, LOZO/LOZO-m, SubZO) draw their factors
from HBM on both lowerings, so the fused Pallas hot path (kernel_mode=
"pallas", interpret mode on CPU) must be numerically interchangeable with
the dense XLA path (kernel_mode="xla") through a full jitted
build_zo_train_step — the end-to-end contract behind repro.core.dispatch.

The MeZO family generates z on-chip from a counter PRNG on the pallas path —
a *different* stream than the XLA path's jax.random.normal — so its
cross-mode parity is statistical (per-leaf update moments) plus exact
within-mode self-consistency (the three Algorithm-1 passes cancel; an lr=0
step is an identity).  The kernel math itself is locked bitwise against
replayed-stream oracles in tests/test_zo_noise.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ZOConfig, build_zo_train_step, init_zo_state
from repro.core.dispatch import KERNEL_METHODS, kernel_execution, resolve_kernel_mode
from repro.core.estimator import METHODS
from repro.kernels import ops


@pytest.fixture(autouse=True)
def _force_interpret():
    ops.set_interpret(True)
    yield
    ops.set_interpret(None)


# A tiny param tree covering every dispatch class: a plain 2-D matrix, a
# leading-batched stack (vmap'd kernel path), and a 1-D dense-fallback bias.
def _params():
    k = jax.random.PRNGKey(17)
    return {
        "w1": jax.random.normal(jax.random.fold_in(k, 0), (16, 24)) * 0.1,
        "stack": jax.random.normal(jax.random.fold_in(k, 1), (2, 12, 12)) * 0.1,
        "b": jnp.zeros((12,)),
    }


def _loss_fn(p, batch):
    h = jnp.tanh(batch["x"] @ p["w1"])[:, :12]          # (B, 12)
    for layer in range(p["stack"].shape[0]):
        h = h + 0.1 * jnp.tanh(h @ p["stack"][layer])
    h = h + p["b"]
    return jnp.mean((jnp.sum(h, axis=-1) - batch["y"]) ** 2)


def _batch():
    k = jax.random.PRNGKey(5)
    return {
        "x": jax.random.normal(k, (4, 16)),
        "y": jnp.ones((4,)),
    }


def _run(method, q_probes, kernel_mode, n_steps=4, **cfg_kw):
    cfg_kw.setdefault("lr", 1e-2)
    # small ν so 4 steps cross a LOZO/SubZO lazy-window boundary
    cfg_kw.setdefault("lazy_interval", 3)
    cfg = ZOConfig(
        method=method, kernel_mode=kernel_mode, rank=4,
        q_probes=q_probes, seed=3, **cfg_kw,
    )
    state = init_zo_state(_params(), cfg)
    step = jax.jit(build_zo_train_step(_loss_fn, cfg))
    batch = _batch()
    metrics = None
    for _ in range(n_steps):
        state, metrics = step(state, batch)
    return state, metrics


# Methods whose perturbation factors come from HBM on both lowerings, so
# pallas-vs-xla agreement is tight ("bitwise-style": same inputs, same f32
# contraction, tolerance only for matmul reassociation).
FACTOR_METHODS = ["tezo", "tezo_m", "tezo_adam", "lozo", "lozo_m", "subzo"]


@pytest.mark.parametrize(
    "method,q_probes",
    [(m, q) for m in FACTOR_METHODS for q in (1, 2)]
    + [("tezo", 4), ("lozo", 4), ("subzo", 4)],   # q-SPSA kernel-path coverage
)
def test_train_step_parity(method, q_probes):
    """Params, optimizer state, and loss metrics agree between the two
    lowerings after several jitted steps — for every factor-carried method
    (the in-kernel / factor-space q-probe accumulation must match the dense
    probe loop it replaced).

    The two lowerings reconstruct Z with different dot orders, so a
    perturbed loss can differ by one ulp, and κ = (f₊−f₋)/2ρ carries that
    ulp times 1/2ρ = 500 into the update.  The run must therefore descend:
    at lr=1e-2 plain TeZO/LOZO with q ≥ 2 diverge (tezo-2's loss climbs
    0.41 → 13.06 in the four steps), each step multiplies that one-ulp κ
    difference about tenfold, and the comparison measures the blow-up, not
    the lowerings.  At lr=3e-3 every case descends and the lowerings agree
    to ~1e-6."""
    s_x, m_x = _run(method, q_probes, "xla", lr=3e-3)
    s_p, m_p = _run(method, q_probes, "pallas", lr=3e-3)

    # each probe adds 3 perturb passes whose ~1-ulp reassociation differences
    # are amplified by κ = (f₊−f₋)/2ρ, so the bound scales with q
    atol = 5e-5 if q_probes <= 2 else 3e-4
    for (path_a, a), (path_b, b) in zip(
        jax.tree_util.tree_leaves_with_path(s_x.params),
        jax.tree_util.tree_leaves_with_path(s_p.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=atol, rtol=1e-4,
            err_msg=f"params diverged at {path_a}",
        )

    for key in ("tau_m", "tau_v", "v_m"):
        if key in s_x.mstate:
            for path in s_x.mstate[key]:
                np.testing.assert_allclose(
                    np.asarray(s_x.mstate[key][path]),
                    np.asarray(s_p.mstate[key][path]),
                    atol=1e-4, rtol=1e-3,
                    err_msg=f"{key} diverged at {path}",
                )

    np.testing.assert_allclose(float(m_x["loss"]), float(m_p["loss"]), atol=1e-4)
    np.testing.assert_allclose(
        float(m_x["kappa_abs"]), float(m_p["kappa_abs"]), atol=1e-3, rtol=1e-2
    )


@pytest.mark.parametrize("method", ["tezo", "tezo_adam"])
def test_train_step_parity_bf16_factors(method):
    """With factor_dtype=bfloat16 (the HBM-halving production setting) the
    two lowerings are NOT bit-comparable by design: the dense path rounds Z
    to bf16 before the add, the kernels accumulate in f32.  The divergence
    must stay at bf16-rounding scale — per-add ~ulp(ρ·Z) on params, and that
    times the 1/2ρ κ-amplification on the τ-space moments.  A short low-lr
    run keeps the comparison at rounding scale instead of compounding
    trajectory divergence."""
    s_x, m_x = _run(method, 1, "xla", n_steps=2, lr=1e-4,
                    factor_dtype=jnp.bfloat16)
    s_p, m_p = _run(method, 1, "pallas", n_steps=2, lr=1e-4,
                    factor_dtype=jnp.bfloat16)
    for a, b in zip(jax.tree.leaves(s_x.params), jax.tree.leaves(s_p.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)
    if "tau_m" in s_x.mstate:
        for path in s_x.mstate["tau_m"]:
            np.testing.assert_allclose(
                np.asarray(s_x.mstate["tau_m"][path]),
                np.asarray(s_p.mstate["tau_m"][path]),
                atol=0.2, rtol=0.05,
            )
    np.testing.assert_allclose(float(m_x["loss"]), float(m_p["loss"]), atol=5e-3)


@pytest.mark.parametrize(
    "method", ["tezo", "tezo_adam", "mezo", "mezo_m", "mezo_adam", "lozo_m", "subzo"]
)
def test_weight_decay_fused_parity(method):
    """cfg.weight_decay folds into the fused update kernels' scalar params
    (no separate full-W decay pass) — the two lowerings must still agree,
    and the decay must actually bite (differ from the wd=0 trajectory)."""
    wd = 0.05
    s_x, m_x = _run(method, 1, "xla", n_steps=3, weight_decay=wd)
    s_p, m_p = _run(method, 1, "pallas", n_steps=3, weight_decay=wd)
    if method.startswith("mezo"):
        # different noise streams by design: check the decay path via the
        # shared loss statistics instead of per-element params
        assert np.isfinite(float(m_p["loss"]))
    else:
        for (path_a, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(s_x.params),
            jax.tree_util.tree_leaves_with_path(s_p.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4,
                err_msg=f"params diverged at {path_a}",
            )
    s_0, _ = _run(method, 1, "pallas", n_steps=3)
    diffs = [
        float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
        for a, b in zip(jax.tree.leaves(s_p.params), jax.tree.leaves(s_0.params))
    ]
    assert max(diffs) > 1e-6, "weight decay had no effect on the pallas path"


def test_fused_decay_matches_decoupled_reference():
    """Leaf-level semantics: decay·W − lr·recon == the decoupled-AdamW order
    of operations (decay the weight, then apply the update) on both paths."""
    from repro.core.cpd import CPDFactor
    from repro.core import dispatch
    from repro.kernels import ref

    key = jax.random.PRNGKey(13)
    w = jax.random.normal(key, (48, 40)) * 0.1
    u = jax.random.normal(jax.random.fold_in(key, 1), (48, 4))
    v = jax.random.normal(jax.random.fold_in(key, 2), (40, 4))
    tau = jax.random.normal(jax.random.fold_in(key, 3), (4,))
    lr, wd = 1e-2, 0.1
    decay = 1.0 - lr * wd
    fac = CPDFactor(u=u, v=v)
    want = ref.tezo_perturb_ref(w, u, v, tau, -lr, decay=decay)
    for use_kernel in (True, False):
        got = dispatch.sgd_update_leaf(
            w, fac, tau, lr, use_kernel=use_kernel, decay=decay
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5, err_msg=str(use_kernel)
        )


def test_parity_exact_restore_mode():
    """Parity must also hold on the exact-restore branch of Algorithm 1."""
    s_x, _ = _run("tezo_adam", 1, "xla", restore_mode="exact")
    s_p, _ = _run("tezo_adam", 1, "pallas", restore_mode="exact")
    for a, b in zip(jax.tree.leaves(s_x.params), jax.tree.leaves(s_p.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_kernel_mode_resolution_and_validation():
    assert resolve_kernel_mode("pallas") == "pallas"
    assert resolve_kernel_mode("xla") == "xla"
    # auto picks the fused kernels exactly when Mosaic is available
    expected = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert resolve_kernel_mode("auto") == expected
    with pytest.raises(ValueError, match="kernel_mode"):
        resolve_kernel_mode("mosaic")
    with pytest.raises(ValueError, match="kernel_mode"):
        build_zo_train_step(_loss_fn, ZOConfig(method="tezo", kernel_mode="bogus"))


# ---------------------------------------------------------------------------
# MeZO family: statistical parity + within-mode self-consistency
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["mezo", "mezo_m", "mezo_adam"])
def test_mezo_lr0_step_is_identity_on_kernel_path(method):
    """The three on-chip-noise passes must cancel inside a full jitted train
    step: with lr=0 the step is an identity on params (f32 ~exact) — the
    self-consistency half of the MeZO parity contract."""
    params = _params()
    cfg = ZOConfig(method=method, kernel_mode="pallas", lr=0.0, seed=3)
    state = init_zo_state(params, cfg)
    step = jax.jit(build_zo_train_step(_loss_fn, cfg))
    for _ in range(3):
        state, metrics = step(state, _batch())
    assert np.isfinite(float(metrics["loss"]))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("q_probes", [1, 4])
def test_mezo_statistical_parity(q_probes):
    """The two lowerings draw different N(0,1) streams by design, so compare
    *statistics* of the SGD update direction g = mean_i κ_i z_i on a large
    leaf: with κ fixed, per-element mean ≈ 0 and std ≈ ‖κ‖/q on both paths
    (131k samples → the std estimate is tight to ~0.4%)."""
    from repro.core import dispatch

    w = jnp.zeros((256, 512), jnp.float32)
    key_t = jax.random.PRNGKey(21)
    kap = jnp.asarray([1.0, -0.5, 0.25, 2.0][:q_probes], jnp.float32)
    want_std = float(jnp.sqrt(jnp.sum(kap * kap))) / q_probes
    g = {}
    for use_kernel in (False, True):
        w2 = dispatch.noise_sgd_update_leaf(
            w, key_t, "['w']", kap, 1.0, use_kernel=use_kernel
        )
        g[use_kernel] = np.asarray(-w2)  # lr=1, w=0 → w' = −g
    for use_kernel, gv in g.items():
        assert abs(gv.mean()) < 5.0 * want_std / np.sqrt(gv.size), use_kernel
        np.testing.assert_allclose(gv.std(), want_std, rtol=0.02)
    # and the two streams really are different realizations
    assert float(np.max(np.abs(g[True] - g[False]))) > 1e-3


def test_mezo_perturb_update_share_a_stream_on_kernel_path():
    """Per-leaf perturb and update must replay the same z within the pallas
    mode (κ-weighted SPSA only makes sense if they do): a single-probe SGD
    update with κ=1, lr=1 must step exactly −z where W + ρz was the perturb
    direction."""
    from repro.core import dispatch

    w = jnp.zeros((64, 128), jnp.float32)
    key_t = jax.random.PRNGKey(22)
    z = (
        dispatch.noise_perturb_leaf(
            w, key_t, "['w']", 0, 1.0, use_kernel=True
        )
        - w
    )
    w2 = dispatch.noise_sgd_update_leaf(
        w, key_t, "['w']", jnp.ones((1,), jnp.float32), 1.0, use_kernel=True
    )
    np.testing.assert_allclose(np.asarray(w2), np.asarray(-z), atol=1e-6)


# ---------------------------------------------------------------------------
# Universal coverage: every method, every leaf class, kernels really used
# ---------------------------------------------------------------------------

# Which ops each method's hot path must invoke under kernel_mode="pallas".
_EXPECTED_OPS = {
    "tezo": {"tezo_perturb"},
    "tezo_m": {"tezo_perturb"},
    "tezo_adam": {"tezo_perturb", "tezo_adam_update"},
    "mezo": {"noise_perturb", "noise_update_sgd"},
    "mezo_m": {"noise_perturb", "noise_update_momentum"},
    "mezo_adam": {"noise_perturb", "noise_update_adam"},
    "lozo": {"lozo_perturb"},
    "lozo_m": {"lozo_perturb"},
    "subzo": {"subzo_perturb"},
}
_ALL_SPIED = sorted(set().union(*_EXPECTED_OPS.values()))


@pytest.mark.parametrize("method", sorted(METHODS))
def test_pallas_path_actually_used(method, monkeypatch):
    """Guard against silent fallback: with kernel_mode="pallas" every
    method's perturb AND update must route through its fused kernels (the
    acceptance criterion for universal dispatch), and with "xla" none may."""
    from repro.core import dispatch

    calls = {name: 0 for name in _ALL_SPIED}

    def make_spy(name, real):
        def spy(*a, **kw):
            calls[name] += 1
            return real(*a, **kw)

        return spy

    for name in _ALL_SPIED:
        monkeypatch.setattr(dispatch.ops, name, make_spy(name, getattr(ops, name)))

    _run(method, 1, "pallas", n_steps=1)
    for name in _EXPECTED_OPS[method]:
        assert calls[name] > 0, (method, name, calls)

    for name in calls:
        calls[name] = 0
    _run(method, 1, "xla", n_steps=1)
    assert all(c == 0 for c in calls.values()), (method, calls)


def test_kernel_execution_reports_pallas_for_every_method():
    """kernel_execution must report path="pallas" for all nine methods under
    kernel_mode="pallas" — the label launchers and benchmarks rely on."""
    assert set(KERNEL_METHODS) == set(METHODS)
    for method in METHODS:
        path, interpret = kernel_execution(method, "pallas")
        assert path == "pallas", method
        assert interpret is True  # forced interpret fixture (CPU)
        path, interpret = kernel_execution(method, "xla")
        assert path == "xla" and interpret is False
