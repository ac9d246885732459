"""Multi-device SPMD tests — run in a subprocess with 8 fake host devices
(tests/_fake_mesh.py) so the rest of the suite keeps seeing exactly 1 device
(assignment §0)."""
import textwrap

import pytest

from _fake_mesh import run_script

_SCRIPT = textwrap.dedent(
    """
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.core import ZOConfig, build_zo_train_step, init_zo_state
    from repro.distributed import (batch_shardings, cache_shardings,
                                   param_shardings, zo_state_shardings)
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.checkpoint import Checkpointer

    mesh = make_host_mesh(data=2, model=4)
    shape = ShapeConfig("t", seq_len=32, global_batch=4, kind="train")

    # ---- sharded ZO step == single-device ZO step -------------------------
    cfg = get_smoke_config("granite-8b").reduced(
        spmd_hints=True, batch_axis_names=("data",))
    model = build_model(cfg)
    model_ref = build_model(cfg.reduced(spmd_hints=False))
    params = model.init(jax.random.PRNGKey(0))
    batch = model.make_inputs(jax.random.PRNGKey(1), shape)
    zo_cfg = ZOConfig(method="tezo_adam", rank=4, lr=1e-4)
    state = init_zo_state(params, zo_cfg)
    step = build_zo_train_step(model.loss_fn, zo_cfg)
    step_ref = build_zo_train_step(model_ref.loss_fn, zo_cfg)

    # single-device reference
    s_ref, m_ref = jax.jit(step_ref)(state, batch)

    # sharded
    state_abs = jax.eval_shape(lambda: state)
    st_sh = zo_state_shardings(mesh, model.logical_axes(), state_abs)
    b_sh = batch_shardings(mesh, jax.eval_shape(lambda: batch))
    step_sharded = jax.jit(step, in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, None))
    with mesh:
        s_got, m_got = step_sharded(jax.device_put(state, st_sh),
                                    jax.device_put(batch, b_sh))
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_got["loss"]), rtol=2e-4)
    for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_got.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-4)
    print("SHARDED_STEP_OK")

    # ---- prefill/decode with sharded cache ---------------------------------
    cfg2 = get_smoke_config("qwen2.5-14b").reduced(
        spmd_hints=True, batch_axis_names=("data",), decode_cache_dtype="float32")
    model2 = build_model(cfg2)
    model2_ref = build_model(cfg2.reduced(spmd_hints=False))
    p2 = model2.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg2.vocab_size)
    toks = toks.astype(jnp.int32)
    logits_ref, cache_ref = jax.jit(lambda p, b: model2_ref.prefill(p, b, 32))(
        p2, {"tokens": toks})
    p_sh = param_shardings(mesh, model2.logical_axes(), model2.abstract_params())
    cache_abs = jax.eval_shape(lambda: cache_ref)
    c_sh = cache_shardings(mesh, cache_abs)
    with mesh:
        prefill_sharded = jax.jit(lambda p, b: model2.prefill(p, b, 32),
                                  in_shardings=(p_sh, None),
                                  out_shardings=(None, c_sh))
        logits_got, cache_got = prefill_sharded(jax.device_put(p2, p_sh),
                                                {"tokens": toks})
        np.testing.assert_allclose(np.asarray(logits_ref), np.asarray(logits_got),
                                   atol=2e-3)
        dec = jax.jit(model2.decode_step, in_shardings=(p_sh, c_sh, None),
                      out_shardings=(None, c_sh))
        tok = jnp.argmax(logits_got, -1).astype(jnp.int32)
        lg, cache_got = dec(jax.device_put(p2, p_sh), cache_got, tok)
        lr_, cache_ref = jax.jit(model2_ref.decode_step)(p2, cache_ref, tok)
        np.testing.assert_allclose(np.asarray(lr_), np.asarray(lg), atol=2e-3)
    print("SHARDED_SERVE_OK")

    # ---- EP shard_map MoE == GSPMD MoE on the same params -----------------
    from repro.distributed.context import set_current_mesh
    set_current_mesh(mesh)
    base = get_smoke_config("dbrx-132b").reduced(moe_capacity_factor=8.0)
    cfg_g = base.reduced(spmd_hints=True, batch_axis_names=("data",), moe_impl="gspmd")
    cfg_e = base.reduced(spmd_hints=True, batch_axis_names=("data",), moe_impl="ep")
    m_gm, m_em = build_model(cfg_g), build_model(cfg_e)
    p_moe = m_gm.init(jax.random.PRNGKey(0))
    b_moe = m_gm.make_inputs(jax.random.PRNGKey(1), shape)
    with mesh:
        lg = jax.jit(m_gm.loss_fn)(p_moe, b_moe)
        le = jax.jit(m_em.loss_fn)(p_moe, b_moe)
    np.testing.assert_allclose(float(lg), float(le), atol=2e-4)
    print("EP_MOE_OK")

    # ---- elastic restore: checkpoint saved unsharded, restored sharded ----
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        ck = Checkpointer(td)
        ck.save(1, state, extra={"step": 1})
        template = jax.eval_shape(lambda: state)
        restored, _ = ck.restore(template, shardings=st_sh)
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(restored.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # every leaf is placed with the target sharding
        leaf = restored.params["blocks"]["wq"]
        assert leaf.sharding.spec == st_sh.params["blocks"]["wq"].spec
    print("ELASTIC_RESTORE_OK")
    """
)


@pytest.mark.slow
def test_multidevice_spmd_suite(tmp_path):
    run_script(
        tmp_path, "spmd_suite.py", _SCRIPT,
        ("SHARDED_STEP_OK", "SHARDED_SERVE_OK", "EP_MOE_OK",
         "ELASTIC_RESTORE_OK"),
        timeout=120,
    )
