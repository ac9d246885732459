"""TPU compile rehearsals: the main-path Pallas kernels at real widths,
compiled by the TPU compiler for a described (not attached) v5e chip.

Interpret mode runs a kernel body as plain JAX, so it accepts blocks,
casts and VMEM footprints that Mosaic refuses.  These tests call the
``kernels/ops.py`` wrappers with Mosaic forced on and compile them for one
chip of a ``v5e:2x2`` topology — nothing runs, so they say nothing about
results or times, only that the chip's compiler takes the kernel.

The topology is described inside a fixture and never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else the compiler logs under /tmp
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with Mosaic forced on and the persistent cache
    off (an entry written for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    ops.set_interpret(False)
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        # the override is process-global: a leaked False would send every
        # later CPU test on this worker to Mosaic
        ops.set_interpret(None)
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


OPT = get_config("opt-125m")
D, F, V, L = OPT.d_model, OPT.d_ff, OPT.vocab_size, OPT.n_layers
H, KV, DH = OPT.n_heads, OPT.n_kv_heads, OPT.head_dim
R = 24  # launch.train's default TeZO rank
HYMBA = get_config("hymba-1.5b")
SCAN_D = HYMBA.d_model * HYMBA.ssm_expand  # the selective scan's channel width
BF, F32, U32, I32 = jnp.bfloat16, jnp.float32, jnp.uint32, jnp.int32
PAGE, SLOTS, PAGES_PER_SLOT, DRAFT = 16, 8, 8, 5


# name -> (fn, [(shape, dtype), ...]); every shape is an opt-125m leaf or
# activation (the scan: hymba-1.5b, the benchmark's hybrid)
CASES = {
    "tezo_perturb": (
        lambda w, u, v, t: ops.tezo_perturb(w, u, v, t, 1e-3),
        [((D, F), BF), ((D, R), F32), ((F, R), F32), ((R,), F32)],
    ),
    "tezo_perturb_stacked": (
        lambda w, u, v, t: ops.tezo_perturb(w, u, v, t, 1e-3),
        [((L, D, F), BF), ((L, D, R), F32), ((L, F, R), F32), ((L, R), F32)],
    ),
    "tezo_adam_update": (
        lambda w, u, v, tm, tv: ops.tezo_adam_update(w, u, v, tm, tv, 1e-3),
        [((D, F), BF), ((D, R), F32), ((F, R), F32), ((R,), F32), ((R,), F32)],
    ),
    "tezo_adam_update_restore": (
        lambda w, u, v, tm, tv, tr: ops.tezo_adam_update(
            w, u, v, tm, tv, 1e-3, tau_r=tr, restore_scale=1e-3
        ),
        [((D, F), BF), ((D, R), F32), ((F, R), F32), ((R,), F32), ((R,), F32),
         ((R,), F32)],
    ),
    # the vocabulary leaves: 50272 rows or columns, which no block divides
    "tezo_perturb_embedding": (
        lambda w, u, v, t: ops.tezo_perturb(w, u, v, t, 1e-3),
        [((V, D), BF), ((V, R), F32), ((D, R), F32), ((R,), F32)],
    ),
    "tezo_perturb_lm_head": (
        lambda w, u, v, t: ops.tezo_perturb(w, u, v, t, 1e-3),
        [((D, V), BF), ((D, R), F32), ((V, R), F32), ((R,), F32)],
    ),
    "noise_perturb_embedding": (
        lambda w, s: ops.noise_perturb(w, s, 1e-3, probe=1),
        [((V, D), BF), ((2,), U32)],
    ),
    "noise_perturb_stacked": (
        lambda w, s: ops.noise_perturb(w, s, 1e-3),
        [((L, D, F), BF), ((2,), U32)],
    ),
    "noise_update_adam": (
        lambda w, m, v, s, k: ops.noise_update_adam(
            w, m, v, s, k, 1e-3, 0.9, 0.999, 1e-8
        ),
        [((D, F), BF), ((D, F), F32), ((D, F), F32), ((2,), U32), ((4,), F32)],
    ),
    "flash_attention": (
        ops.flash_attention,
        [((8, 128, H, DH), BF), ((8, 128, KV, DH), BF), ((8, 128, KV, DH), BF)],
    ),
    "flash_attention_2k": (
        ops.flash_attention,
        [((1, 2048, H, DH), BF), ((1, 2048, KV, DH), BF), ((1, 2048, KV, DH), BF)],
    ),
    "paged_decode_attention": (
        ops.paged_decode_attention,
        [
            ((SLOTS, H, DH), BF),
            ((SLOTS * PAGES_PER_SLOT + 1, PAGE, KV, DH), BF),
            ((SLOTS * PAGES_PER_SLOT + 1, PAGE, KV, DH), BF),
            ((SLOTS, PAGES_PER_SLOT), I32),
            ((SLOTS,), I32),
        ],
    ),
    "paged_verify_attention": (
        ops.paged_verify_attention,
        [
            ((SLOTS, DRAFT, H, DH), BF),
            ((SLOTS * PAGES_PER_SLOT + 1, PAGE, KV, DH), BF),
            ((SLOTS * PAGES_PER_SLOT + 1, PAGE, KV, DH), BF),
            ((SLOTS, PAGES_PER_SLOT), I32),
            ((SLOTS,), I32),
        ],
    ),
    # lut4: 8 codes per uint32 word; K = d_ff is the widest resident K tile
    "quant_matmul_lut4": (
        lambda x, c, lut, xu, qv: ops.quant_matmul(x, c, lut, xu, qv, bits=4),
        [((1024, F), BF), ((F // 8, D), U32), ((D, 16), F32), ((1024, R), F32),
         ((D, R), F32)],
    ),
    "selective_scan": (
        ops.selective_scan,
        [((2, 512, SCAN_D), F32), ((2, 512, SCAN_D), F32),
         ((SCAN_D, HYMBA.ssm_state), F32), ((2, 512, HYMBA.ssm_state), F32),
         ((2, 512, HYMBA.ssm_state), F32), ((2, SCAN_D, HYMBA.ssm_state), F32)],
    ),
}


# the vocabulary leaves are covered by a partial last block: the program
# pads no weight, and the embedding's temporaries stay under its own bytes
NO_PAD = {"tezo_perturb_embedding", "tezo_perturb_lm_head"}
TEMP_BELOW = {"tezo_perturb_embedding": V * D * 2}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), f"{name}: no Mosaic kernel"
    mem = compiled.memory_analysis()
    assert mem is not None
    if name in NO_PAD:
        # a pad of the bf16 leaf (the f32 factors' rank pad is expected)
        assert not re.search(r"= bf16\[\S* pad\(", compiled.as_text()), name
    if name in TEMP_BELOW:
        assert mem.temp_size_in_bytes < TEMP_BELOW[name], (
            name, mem.temp_size_in_bytes)
