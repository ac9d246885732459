"""The plain reference against the program at a small size on the CPU,
and the comparison's power: the fp8 control and the planted faults of
the timed path must come out not correct.

The small model has every kind of leaf the cells have: stacked matrices,
norm gains stacked over 8 layers (so they take low-rank factors, as at
full depth) and the 1-D final gain (dense noise).  The Pallas kernels run
in interpret mode here.

It holds float32 weights.  At this width a perturbation of rho = 1e-3
moves the loss by so little that bfloat16 rounding decides kappa (sound
bf16 runs read gradient gaps up to 0.7 here), while at the cells' widths
rho Z is a third of a weight and kappa is well resolved; in float32 the
small model resolves it too, so the comparison keeps its power.
"""
import copy
import json
import os
import subprocess
import sys
import time

import pytest

from bench import cell as cells

TRAFFIC = json.loads((cells.BENCH / "traffic" / "zo-short.json").read_text())
TRAFFIC.update(batch=4, seq=16, distinct_batches=4, rank=4)
# limits for this size, set as the cells' are: above what sound float32
# runs read, below the control and the faults
LIMITS = {"loss_gap": 1e-4, "grad_gap": 0.02, "change_gap": 0.01}


def tiny_cell(activation="swiglu", dtype="float32", n_layers=8):
    model = {"family": "dense", "n_layers": n_layers, "d_model": 64,
             "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
             "vocab_size": 256, "activation": activation,
             "rope_theta": 10000.0, "norm_eps": 1e-5, "dtype": dtype}
    e2e = [{"name": n} for n in
           ("train_tokens_per_s", "train_peak_hbm_gib", "setup_s")]
    driver = cells.load_module(cells.BENCH / "drivers" / "zo.py", "zo")
    return cells.Cell("tiny.zo", 1, {"name": "tiny", "model": model},
                      copy.deepcopy(TRAFFIC), dict(LIMITS), e2e, [], driver)


def test_reference_matches_program_in_float32():
    """With float32 weights both sides compute the same arithmetic: the
    step's losses, gradient and weights agree to float32 rounding."""
    c = tiny_cell(activation="gelu")
    got, ref = c.driver.readings(c, seed=2**33 + 7)
    checks = c.driver.compare(got, ref, LIMITS)
    assert checks["loss_gap"]["value"] < 1e-5
    assert checks["grad_gap"]["value"] < 1e-2
    assert checks["change_gap"]["value"] < 1e-3


@pytest.mark.parametrize("seed", [11, 3_000_000_017])
def test_program_passes_and_control_fails(seed):
    c = tiny_cell()
    prog = c.driver.compare(*c.driver.readings(c, seed), LIMITS)
    assert all(v["value"] <= v["limit"] for v in prog.values()), prog
    ctrl = c.driver.compare(*c.driver.readings(c, seed, "control"), LIMITS)
    assert any(v["value"] > v["limit"] for v in ctrl.values()), ctrl


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_run_with_a_broken_timed_path(fault):
    """The whole run but the chip check, with the step broken underneath:
    a step that returns its state unchanged, or that takes the loss over
    half of the batch, comes out not correct; the sound step correct."""
    c = tiny_cell()
    out = c.driver.run(c, 5_000_000_011, 0.5, False, time.perf_counter(),
                       fault=fault)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is (fault is None), out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s",
                                   "train_peak_hbm_gib", "setup_s"}
    assert list(out)[-1] == "checks"


def test_refuses_off_the_chip():
    """Off a TPU the command prints no result and exits non-zero."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "opt-13b.zo-short",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr
