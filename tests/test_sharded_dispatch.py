"""Shard-aware fused-kernel dispatch (core.dispatch shard_context).

Run in subprocesses with 8 fake host devices (tests/_fake_mesh.py, which
holds the scripts) so the rest of the suite keeps seeing exactly 1 device
(assignment §0).  Three contracts:

  1. Parity: a jitted ZO step on a 2×4 (data, model) mesh under
     kernel_mode="pallas" (shard_map'd local-shard kernels, interpret mode
     on CPU) matches the plain single-device kernel_mode="xla" step — for a
     TeZO-family method with weight decay (factor state placed by
     mstate_shardings) at q_probes=2, which routes through the CHAINED
     transitions (bridge + restore_into_update, the default schedule);
     chained == unchained bitwise on the mesh; and a MeZO lr=0 sharded
     step is an identity (the on-chip-noise passes cancel device-locally).

  2. Mesh-layout invariance of the zo_noise counter stream: the same
     (key_t, path, probe, global element) draws bitwise-identical z on a
     1-device run and on 8-device meshes of any layout (8×1, 2×4, 1×8),
     including an awkward-dim leaf (vocab-sized 50257 rows, pad-and-mask
     local tiling) and a leading-batch-sharded stack (per-slice seed
     derivation offset by the global slice index).

  3. Forward parity: the shard_map'd forward kernels and a whole
     batch-sharded model forward match their unsharded counterparts.

Probe-parallel parity (cfg.probe_parallel BITWISE equal to the sequential
chained schedule for every registered method) is in
tests/test_probe_parallel_{tezo,mezo,lozo}.py, one file per method family.
"""
import pytest

from _fake_mesh import (
    FORWARD_SCRIPT,
    INVARIANCE_SCRIPT,
    PARITY_SCRIPT,
    run_script,
)


@pytest.mark.slow
def test_sharded_dispatch_parity(tmp_path):
    """pallas(shard_map) on a 2x4 mesh == xla single-device for TeZO-family
    methods (fused weight decay included); MeZO lr=0 sharded step is an
    identity."""
    run_script(
        tmp_path, "sharded_parity.py", PARITY_SCRIPT,
        (
            "PARITY_TEZO_ADAM_OK",
            "PARITY_SUBZO_OK",
            "CHAINED_SHARDED_TEZO_ADAM_OK",
            "CHAINED_SHARDED_MEZO_OK",
            "MEZO_LR0_IDENTITY_OK",
        ),
        timeout=180,
    )


@pytest.mark.slow
def test_sharded_forward_dispatch_parity(tmp_path):
    """The forward kernels are shard-aware under the PR-3 shard context:
    shard_map'd flash attention / selective scan on a 2x4 mesh == the
    unsharded kernels (leaf level), and a whole batch-sharded model forward
    under kernel_mode="pallas" == the single-device xla loss."""
    run_script(
        tmp_path, "sharded_forward.py", FORWARD_SCRIPT,
        (
            "ATTN_LEAF_SHARDED_OK",
            "SCAN_LEAF_SHARDED_OK",
            "MODEL_FORWARD_SHARDED_OK",
        ),
        timeout=90,
    )


@pytest.mark.slow
def test_noise_stream_mesh_layout_invariance(tmp_path):
    """The zo_noise counter stream is bitwise mesh-layout-invariant: same
    (key_t, probe, global coords) → same z on 1 vs 8 devices, any layout,
    including an awkward 50257-row leaf and a batch-sharded stack."""
    run_script(
        tmp_path, "noise_invariance.py", INVARIANCE_SCRIPT,
        (
            "CLEAN_LEAF_INVARIANT_OK",
            "VOCAB_LEAF_INVARIANT_OK",
            "STACK_LEAF_INVARIANT_OK",
            "PAIR_LEAF_INVARIANT_OK",
            "THREE_PASS_SHARDED_OK",
        ),
        timeout=120,
    )
