"""The serving driver at a small size on the CPU: a sound engine passes
the logit check, the fp8 control and an engine that alters the tokens
it samples do not.  The whole run but the chip check is driven."""
import copy
import json
import time

import numpy as np
import pytest

from bench import cell as cells

TRAFFIC = json.loads((cells.BENCH / "traffic" / "serve-task.json").read_text())
TRAFFIC.update(rate_per_s=20.0, max_prompt_len=64, max_new_tokens=16,
               slots=4, check_tokens=40,
               prompt={"median": 20, "sigma": 0.8, "min": 4, "max": 60},
               output={"median": 8, "sigma": 0.5, "min": 2, "max": 16})
LIMITS = {"logit_gap": 0.05}


def tiny_cell():
    model = {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
             "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
             "activation": "swiglu", "rope_theta": 10000.0,
             "norm_eps": 1e-5, "dtype": "bfloat16"}
    e2e = [{"name": n} for n in ("ttft_p95_ms", "itl_p95_ms", "setup_s")]
    driver = cells.load_module(cells.BENCH / "drivers" / "serve.py", "serve")
    return cells.Cell("tiny.serve", 1, {"name": "tiny", "model": model},
                      copy.deepcopy(TRAFFIC), dict(LIMITS), e2e, [], driver)


def test_requests_are_the_same_work_for_every_seed():
    c = tiny_cell()
    a = c.driver.make_requests(c.model, c.traffic, 1, 2.0)
    b = c.driver.make_requests(c.model, c.traffic, 2**33 + 1, 2.0)
    assert len(a) == len(b) == 40
    assert sorted(len(t) for _, t, _ in a) == sorted(len(t) for _, t, _ in b)
    assert sorted(n for _, _, n in a) == sorted(n for _, _, n in b)
    assert all(x[0] <= 2.0 for x in a)
    again = c.driver.make_requests(c.model, c.traffic, 1, 2.0)
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(a, again))


@pytest.mark.parametrize("fault", [None, "altered_token"])
def test_run_with_a_broken_timed_path(fault):
    c = tiny_cell()
    out = c.driver.run(c, 987_654_321_012, 1.0, False, time.perf_counter(),
                       fault=fault)
    assert out["failed"] == 0 and out["attempted"] == 20
    assert out["correct"] is (fault is None), out["checks"]
    assert set(out["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"


def test_control_fails():
    c = tiny_cell()
    gap, n = c.driver.readings(c, 5, 1.0, "control")
    assert n >= c.traffic["check_tokens"]
    assert gap > LIMITS["logit_gap"]


def test_traffic_without_a_measured_rate_is_refused():
    c = tiny_cell()
    del c.traffic["rate_per_s"]
    with pytest.raises(cells.CellError, match="rate_per_s"):
        c.driver.make_requests(c.model, c.traffic, 1, 2.0)
