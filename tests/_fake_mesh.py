"""Tests on 8 fake host devices: one subprocess runner, and the scripts of
the shard-aware dispatch and probe-parallel tests.

The suite's own process sees exactly 1 device (conftest), so a test that
needs a mesh runs a script in a fresh interpreter whose ``XLA_FLAGS`` ask the
host platform for 8 devices.  Every caller gives its subprocess a time limit
of about 3x its time in the 6-worker suite, never over 400 s, so a hang fails
that one test instead of running the whole suite into its limit.

The dispatch scripts enable ``jax_threefry_partitionable`` (as the sharded
launchers do): the *dense-fallback* leaves draw from ``jax.random``, whose
legacy non-partitionable lowering produces a different stream inside a
multi-device pjit than on one device — the counter-PRNG kernel leaves need
no flag, their streams are mesh-invariant by construction.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

_SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_script(tmp_path, name, script, markers, timeout):
    """Run ``script`` on 8 fake host devices; it must exit 0 and print every
    marker within ``timeout`` seconds."""
    path = tmp_path / name
    path.write_text(script)
    env = dict(
        os.environ, PYTHONPATH=_SRC,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    proc = subprocess.run(
        [sys.executable, str(path)], env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    for marker in markers:
        assert marker in proc.stdout, (marker, proc.stdout[-2000:])


# The ZO step scripts' common start: a 2x4 (data, model) mesh and a tiny
# tree covering every dispatch class: plain 2-D (row+col sharded), a
# leading-batched stack, and a 1-D dense-fallback bias.
_TINY_ZO = textwrap.dedent(
    """
    import jax
    jax.config.update("jax_threefry_partitionable", True)
    import numpy as np
    import jax.numpy as jnp

    from repro.core import ZOConfig, build_zo_train_step, init_zo_state
    from repro.launch.mesh import make_host_mesh
    from repro.kernels import ops

    ops.set_interpret(True)
    mesh = make_host_mesh(data=2, model=4)

    params = {
        "w1": jax.random.normal(jax.random.PRNGKey(0), (32, 64)) * 0.1,
        "stack": jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16)) * 0.1,
        "b": jnp.zeros((16,)),
    }

    def loss_fn(p, batch):
        h = jnp.tanh(batch["x"] @ p["w1"])[:, :16]
        for layer in range(p["stack"].shape[0]):
            h = h + 0.1 * jnp.tanh(h @ p["stack"][layer])
        h = h + p["b"]
        return jnp.mean((jnp.sum(h, axis=-1) - batch["y"]) ** 2)

    batch = {"x": jax.random.normal(jax.random.PRNGKey(5), (4, 32)),
             "y": jnp.ones((4,))}
    """
)


PARITY_SCRIPT = _TINY_ZO + textwrap.dedent(
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import param_spec_table, zo_state_shardings

    axes = {"w1": ("embed", "ff"), "stack": ("layers", "embed", "ff"),
            "b": (None,)}

    def sharded_state(state):
        st_sh = zo_state_shardings(mesh, axes, jax.eval_shape(lambda: state))
        return st_sh, param_spec_table(st_sh.params)

    # ---- TeZO-family parity: pallas(shard_map, 2x4) == xla(single device),
    # with the weight decay fused into the sharded kernels.  q_probes=2
    # exercises the CHAINED transitions (bridge + restore_into_update —
    # the default restore_mode="inplace" schedule) through the shard_map'd
    # stacked-factor / dual-draw kernels. ---------------------------------
    for method in ("tezo_adam", "subzo"):
        cfg_x = ZOConfig(method=method, kernel_mode="xla", rank=4, lr=1e-2,
                         seed=3, weight_decay=0.05, lazy_interval=3,
                         q_probes=2)
        cfg_p = ZOConfig(method=method, kernel_mode="pallas", rank=4, lr=1e-2,
                         seed=3, weight_decay=0.05, lazy_interval=3,
                         q_probes=2)
        state = init_zo_state(params, cfg_x)
        step_ref = jax.jit(build_zo_train_step(loss_fn, cfg_x))
        s_ref, m_ref = state, None
        for _ in range(2):
            s_ref, m_ref = step_ref(s_ref, batch)

        state_p = init_zo_state(params, cfg_p)
        st_sh, specs = sharded_state(state_p)
        if method == "tezo_adam":
            # factor/τ state really is placed by mstate_shardings: u rides
            # the leaf's row sharding, v the column sharding, τ replicated
            fac_sh = st_sh.mstate["factors"]["['w1']"]
            assert fac_sh.u.spec == P("data", None), fac_sh.u.spec
            assert fac_sh.v.spec == P("model", None), fac_sh.v.spec
            assert st_sh.mstate["tau_m"]["['w1']"].spec == P()
        step_sh = jax.jit(
            build_zo_train_step(loss_fn, cfg_p, mesh=mesh, param_specs=specs),
            in_shardings=(st_sh, None), out_shardings=(st_sh, None),
        )
        with mesh:
            s_got, m_got = jax.device_put(state_p, st_sh), None
            for _ in range(2):
                s_got, m_got = step_sh(s_got, batch)
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(s_ref.params),
            jax.tree_util.tree_leaves_with_path(s_got.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4,
                err_msg=f"{method} params diverged at {pa}",
            )
        np.testing.assert_allclose(
            float(m_ref["loss"]), float(m_got["loss"]), rtol=2e-4
        )
        print(f"PARITY_{method.upper()}_OK")

    # ---- chained == unchained BITWISE on the mesh: the shard_map'd bridge /
    # restore-into-update kernels reproduce the separate passes exactly ----
    for method in ("tezo_adam", "mezo"):
        outs = {}
        for restore_mode in ("inplace", "unchained"):
            cfg_c = ZOConfig(method=method, kernel_mode="pallas", rank=4,
                             lr=1e-2, seed=3, q_probes=2,
                             restore_mode=restore_mode)
            state_c = init_zo_state(params, cfg_c)
            st_sh, specs = sharded_state(state_c)
            step_c = jax.jit(
                build_zo_train_step(loss_fn, cfg_c, mesh=mesh,
                                    param_specs=specs),
                in_shardings=(st_sh, None), out_shardings=(st_sh, None),
            )
            with mesh:
                s = jax.device_put(state_c, st_sh)
                for _ in range(2):
                    s, _ = step_c(s, batch)
            outs[restore_mode] = s
        for a, b in zip(jax.tree.leaves(outs["inplace"].params),
                        jax.tree.leaves(outs["unchained"].params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print(f"CHAINED_SHARDED_{method.upper()}_OK")

    # ---- MeZO lr=0: the sharded pallas step is an identity on params ----
    cfg0 = ZOConfig(method="mezo", kernel_mode="pallas", lr=0.0, seed=3)
    state0 = init_zo_state(params, cfg0)
    st_sh, specs = sharded_state(state0)
    step0 = jax.jit(
        build_zo_train_step(loss_fn, cfg0, mesh=mesh, param_specs=specs),
        in_shardings=(st_sh, None), out_shardings=(st_sh, None),
    )
    with mesh:
        s0 = jax.device_put(state0, st_sh)
        for _ in range(3):
            s0, metrics0 = step0(s0, batch)
    assert np.isfinite(float(metrics0["loss"]))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(s0.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    print("MEZO_LR0_IDENTITY_OK")
    """
)


INVARIANCE_SCRIPT = textwrap.dedent(
    """
    import jax
    jax.config.update("jax_threefry_partitionable", True)
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import dispatch
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh

    ops.set_interpret(True)
    key_t = jax.random.PRNGKey(21)

    def layout_run(data, model, spec, w, probe):
        mesh = make_host_mesh(data=data, model=model)
        sh = NamedSharding(mesh, spec)

        def f(w):
            with dispatch.shard_context(mesh, {"['w']": spec}):
                return dispatch.noise_perturb_leaf(
                    w, key_t, "['w']", probe, 1.0, use_kernel=True
                )

        with mesh:
            out = jax.jit(f, in_shardings=(sh,), out_shardings=sh)(
                jax.device_put(w, sh)
            )
        return np.asarray(out)

    # reference: unsharded single-device kernel draw (global coordinates)
    def ref_run(w, probe):
        return np.asarray(
            dispatch.noise_perturb_leaf(
                w, key_t, "['w']", probe, 1.0, use_kernel=True
            )
        )

    # clean-dim leaf: every layout must replay the identical stream
    w = jnp.zeros((1024, 512), jnp.float32)
    want = ref_run(w, 1)
    for data, model, spec in [
        (8, 1, P("data", None)),          # 8-way FSDP rows
        (1, 8, P(None, "model")),         # 8-way TP columns
        (2, 4, P("data", "model")),       # 2x4 both dims
        (2, 4, P(None, None)),            # fully replicated under a mesh
    ]:
        got = layout_run(data, model, spec, w, 1)
        np.testing.assert_array_equal(got, want, err_msg=str(spec))
    print("CLEAN_LEAF_INVARIANT_OK")

    # awkward-dim leaf: 50257 rows (opt-125m vocab) — local pad-and-mask
    # tiling may pad differently per layout; the stream must not care
    wv = jnp.zeros((50257, 768), jnp.float32)
    want_v = ref_run(wv, 2)
    got_v = layout_run(1, 8, P(None, "model"), wv, 2)
    np.testing.assert_array_equal(got_v, want_v)
    print("VOCAB_LEAF_INVARIANT_OK")

    # leading-batch-sharded stack: per-slice seeds must use global indices
    ws = jnp.zeros((8, 32, 128), jnp.float32)
    want_s = ref_run(ws, 0)
    got_s = layout_run(8, 1, P("data", None, None), ws, 0)
    np.testing.assert_array_equal(got_s, want_s)
    # and distinct slices still draw distinct streams
    assert np.abs(got_s[0] - got_s[1]).max() > 1e-3
    print("STACK_LEAF_INVARIANT_OK")

    # dual-draw chained bridge: mesh-layout-invariant like the single draw
    # (same global-coordinate counters for BOTH probes in one tile visit)
    wp = jnp.zeros((1024, 512), jnp.float32)
    want_p = np.asarray(dispatch.noise_perturb_pair_leaf(
        wp, key_t, "['w']", 1, 1e-3, 2, 1e-3, use_kernel=True
    ))
    for data, model, spec in [(8, 1, P("data", None)), (2, 4, P("data", "model"))]:
        mesh_p = make_host_mesh(data=data, model=model)
        sh_p = NamedSharding(mesh_p, spec)

        def fp(w):
            with dispatch.shard_context(mesh_p, {"['w']": spec}):
                return dispatch.noise_perturb_pair_leaf(
                    w, key_t, "['w']", 1, 1e-3, 2, 1e-3, use_kernel=True
                )

        with mesh_p:
            got_p = jax.jit(fp, in_shardings=(sh_p,), out_shardings=sh_p)(
                jax.device_put(wp, sh_p)
            )
        np.testing.assert_array_equal(np.asarray(got_p), want_p, err_msg=str(spec))
    print("PAIR_LEAF_INVARIANT_OK")

    # three-pass replay on a sharded leaf: +rho, -2rho, +rho cancels
    wr = jax.random.normal(jax.random.PRNGKey(3), (256, 512)) * 0.1
    mesh = make_host_mesh(data=2, model=4)
    sh = NamedSharding(mesh, P("data", "model"))

    def three_pass(w):
        with dispatch.shard_context(mesh, {"['w']": P("data", "model")}):
            p = dispatch.noise_perturb_leaf(
                w, key_t, "['w']", 0, +1e-3, use_kernel=True
            )
            p = dispatch.noise_perturb_leaf(
                p, key_t, "['w']", 0, -2e-3, use_kernel=True
            )
            return dispatch.noise_perturb_leaf(
                p, key_t, "['w']", 0, +1e-3, use_kernel=True
            )

    with mesh:
        restored = jax.jit(three_pass, in_shardings=(sh,), out_shardings=sh)(
            jax.device_put(wr, sh)
        )
    assert float(jnp.max(jnp.abs(restored - wr))) <= 1e-6
    print("THREE_PASS_SHARDED_OK")
    """
)


FORWARD_SCRIPT = textwrap.dedent(
    """
    import jax
    jax.config.update("jax_threefry_partitionable", True)
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.core import dispatch
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.distributed import batch_shardings
    from repro.distributed.sharding import param_shardings

    ops.set_interpret(True)
    mesh = make_host_mesh(data=2, model=4)

    # ---- leaf level: the shard_map'd flash kernel on a batch-sharded (and,
    # when head dims divide the model axis, head-sharded) activation draws
    # the same output as the unsharded kernel ------------------------------
    key = jax.random.PRNGKey(11)
    for H, KV, hspec in [
        (4, 2, None),       # KV % model-size != 0 -> batch-only shard_map
        (8, 4, "model"),    # GQA heads ride the TP axis (local KV groups)
    ]:
        q = jax.random.normal(key, (8, 60, H, 24)) * 0.3   # awkward S and dh
        k = jax.random.normal(jax.random.fold_in(key, 1), (8, 60, KV, 24)) * 0.3
        v = jax.random.normal(jax.random.fold_in(key, 2), (8, 60, KV, 24)) * 0.3
        want = dispatch.attention_fwd(
            q, k, v, window=17, mode="pallas", batch_axes=("data",)
        )
        sh = NamedSharding(mesh, P("data", None, hspec, None))

        def f(q, k, v):
            with dispatch.shard_context(mesh, {}):
                return dispatch.attention_fwd(
                    q, k, v, window=17, mode="pallas", batch_axes=("data",)
                )

        with mesh:
            got = jax.jit(f, in_shardings=(sh, sh, sh), out_shardings=sh)(
                jax.device_put(q, sh), jax.device_put(k, sh),
                jax.device_put(v, sh)
            )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, err_msg=str(hspec)
        )
    print("ATTN_LEAF_SHARDED_OK")

    # ---- and the shard_map'd selective scan ------------------------------
    B, S, D, N = 8, 40, 24, 4
    x = jax.random.normal(key, (B, S, D)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3), (B, S, D)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 4), (D, N)) * 0.3)
    bb = jax.random.normal(jax.random.fold_in(key, 5), (B, S, N)) * 0.5
    cc = jax.random.normal(jax.random.fold_in(key, 6), (B, S, N)) * 0.5
    h0 = jax.random.normal(jax.random.fold_in(key, 7), (B, D, N)) * 0.1
    wy, wh = dispatch.selective_scan_fwd(
        x, dt, a, bb, cc, h0, mode="pallas", batch_axes=("data",)
    )
    s3 = NamedSharding(mesh, P("data", None, None))

    def g(x, dt, a, bb, cc, h0):
        with dispatch.shard_context(mesh, {}):
            return dispatch.selective_scan_fwd(
                x, dt, a, bb, cc, h0, mode="pallas", batch_axes=("data",)
            )

    rep2 = NamedSharding(mesh, P(None, None))
    with mesh:
        gy, gh = jax.jit(
            g,
            in_shardings=(s3, s3, rep2, s3, s3, s3),
            out_shardings=(s3, s3),
        )(*(jax.device_put(t, s)
            for t, s in zip((x, dt, a, bb, cc, h0), (s3, s3, rep2, s3, s3, s3))))
    np.testing.assert_allclose(np.asarray(gy), np.asarray(wy), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(wh), atol=1e-5)
    print("SCAN_LEAF_SHARDED_OK")

    # ---- model level: a whole sharded forward (flash kernels inside the
    # layer scan, shard_map inside pjit) matches the single-device xla loss -
    shape = ShapeConfig("t", seq_len=24, global_batch=8, kind="train")
    base = get_smoke_config("opt-125m").reduced(batch_axis_names=("data",))
    # reference runs on one device with no mesh -> no spmd hints there
    model_x = build_model(base.reduced(kernel_mode="xla"))
    model_p = build_model(base.reduced(kernel_mode="pallas", spmd_hints=True))
    params = model_x.init(jax.random.PRNGKey(0))
    batch = model_x.make_inputs(jax.random.PRNGKey(1), shape)
    want_loss = float(model_x.loss_fn(params, batch))

    p_sh = param_shardings(mesh, model_p.logical_axes(), model_p.abstract_params())
    b_sh = batch_shardings(mesh, jax.eval_shape(lambda: batch))

    def loss_sharded(p, b):
        with dispatch.shard_context(mesh, {}):
            return model_p.loss_fn(p, b)

    with mesh:
        got_loss = float(
            jax.jit(loss_sharded, in_shardings=(p_sh, b_sh))(
                jax.device_put(params, p_sh), jax.device_put(batch, b_sh)
            )
        )
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-5)
    print("MODEL_FORWARD_SHARDED_OK")
    """
)


_PP_PARITY_SCRIPT = _TINY_ZO + textwrap.dedent(
    """
    from repro.core.zo_step import zo_pass_count
    from repro.distributed import replicated_tree

    METHOD = __METHOD__
    for q in __QS__:
        for km in ("pallas", "xla"):
            common = dict(method=METHOD, kernel_mode=km, rank=4, lr=1e-2,
                          seed=3, weight_decay=0.05, lazy_interval=3,
                          q_probes=q)
            cfg_s = ZOConfig(**common)
            s_ref = init_zo_state(params, cfg_s)
            step_ref = jax.jit(build_zo_train_step(loss_fn, cfg_s))
            m_ref = None
            for _ in range(2):
                s_ref, m_ref = step_ref(s_ref, batch)

            # q=3 on the 2-lane data axis is the uneven split: lane 0 runs
            # probes {0, 1}, lane 1 catches up through probe 1's triple and
            # runs probe 2 alone
            cfg_p = ZOConfig(**common, probe_parallel=True)
            state_p = init_zo_state(params, cfg_p)
            # placed as launch/train.py places them: state and batch
            # replicated in, state replicated out, so step 2 runs step 1's
            # executable
            state_sh = replicated_tree(mesh, jax.eval_shape(lambda: state_p))
            batch_sh = replicated_tree(mesh, jax.eval_shape(lambda: batch))
            step_pp = jax.jit(
                build_zo_train_step(loss_fn, cfg_p, mesh=mesh, param_specs={}),
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
            )
            m_got = None
            with mesh:
                s_got = jax.device_put(state_p, state_sh)
                for _ in range(2):
                    s_got, m_got = step_pp(s_got, batch)
            assert step_pp._cache_size() == 1, step_pp._cache_size()

            for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(
                    (s_ref.params, s_ref.mstate)
                ),
                jax.tree_util.tree_leaves_with_path(
                    (s_got.params, s_got.mstate)
                ),
            ):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"{METHOD} q={q} {km} at {jax.tree_util.keystr(pa)}",
                )
            np.testing.assert_array_equal(
                np.asarray(m_ref["loss"]), np.asarray(m_got["loss"])
            )
            np.testing.assert_array_equal(
                np.asarray(m_ref["kappa_abs"]), np.asarray(m_got["kappa_abs"])
            )
            assert int(m_got["zo_passes"]) == zo_pass_count(
                q, "inplace", probe_lanes=2
            ), (int(m_got["zo_passes"]), q)
            print(f"PP_{METHOD}_q{q}_{km}_OK")
    print(f"PP_{METHOD}_ALL_OK")
    """
)


def run_probe_parallel_parity(tmp_path, method):
    """Probe-parallel (cfg.probe_parallel, q probes sharded over the 2-lane
    data axis of the 2×4 mesh) == the sequential chained schedule BITWISE —
    params, method state, and loss/κ metrics — for q∈{2,4} on both
    lowerings, two steps (state carry included), the probe-parallel step
    compiled once.  tezo_adam additionally runs q=3: the uneven split where
    lane 1 opens with a catch-up chain and holds fewer probes than lane 0.
    The recorded zo_passes metric must be the per-replica 2·ceil(q/D)+1, not
    the sequential 2q+1."""
    qs = (2, 3, 4) if method == "tezo_adam" else (2, 4)
    script = (
        _PP_PARITY_SCRIPT
        .replace("__METHOD__", repr(method))
        .replace("__QS__", repr(qs))
    )
    markers = tuple(
        f"PP_{method}_q{q}_{km}_OK" for q in qs for km in ("pallas", "xla")
    ) + (f"PP_{method}_ALL_OK",)
    run_script(
        tmp_path, f"pp_parity_{method}.py", script, markers, timeout=300
    )
