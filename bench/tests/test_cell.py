"""A cell that names an unknown configuration, traffic, driver or metric
fails loudly, before anything runs."""
import copy
import json

import pytest

from bench import cell as cells

BM = json.loads((cells.ROOT / "BENCHMARK.json").read_text())


def _load(bm, name=None):
    return cells.load(name or bm["workloads"][0]["name"], bm)


def test_every_cell_loads():
    for w in BM["workloads"]:
        c = cells.load(w["name"], BM)
        assert c.chips == w["chips"]
        assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
        assert c.per_layer, "every cell reports a per-layer metric"


def test_unknown_workload():
    with pytest.raises(cells.CellError, match="unknown workload"):
        _load(BM, "no-such-cell")


def test_unknown_configuration():
    bm = copy.deepcopy(BM)
    bm["workloads"][0]["config"] = "no-such-config"
    with pytest.raises(cells.CellError, match="unknown configuration"):
        _load(bm)


def test_missing_configuration_file():
    bm = copy.deepcopy(BM)
    bm["configs"][0]["file"] = "bench/configs/no-such-file.json"
    name = next(w["name"] for w in bm["workloads"]
                if w["config"] == bm["configs"][0]["name"])
    with pytest.raises(cells.CellError, match="no file"):
        _load(bm, name)


def test_unknown_traffic():
    bm = copy.deepcopy(BM)
    bm["workloads"][0]["traffic"] = "no-such-traffic"
    with pytest.raises(cells.CellError, match="traffic no-such-traffic"):
        _load(bm)


def test_unknown_driver(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "t.json").write_text('{"driver": "no-such"}')
    bm = copy.deepcopy(BM)
    bm["workloads"][0]["traffic"] = "t"
    with pytest.raises(cells.CellError, match="driver no-such"):
        cells.load(bm["workloads"][0]["name"], bm, bench_dir=tmp_path)


def test_unknown_metric():
    bm = copy.deepcopy(BM)
    bm["per_layer"].append({"name": "no_such_metric", "unit": "%",
                            "better": "higher", "source": "device_trace",
                            "layer": "device", "moves": "setup_s"})
    with pytest.raises(cells.CellError, match="metric no_such_metric"):
        _load(bm)


def test_declared_metric_that_reads_nothing_fails():
    """A per-layer metric declared for the cell that finds nothing in the
    trace stops the run; it is not silently left out of the line."""
    from bench import counts

    c = _load(BM)
    ctx = {"counts": counts, "kind": "zo", "steps": 4, "busy_s": 1.0, "window_s": 1.0,
           "host_window_s": 1.0, "ops": [{"name": "fusion.1",
                                          "text": "fusion.1",
                                          "seconds": 1.0}],
           "model": c.model, "traffic": c.traffic,
           "peak": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e11}}
    with pytest.raises(cells.CellError, match="read nothing"):
        cells.read_per_layer(c, ctx)


def test_forward_ms_needs_the_pass_kernels():
    """Without a pass kernel in the trace, busy time less the passes
    would be the whole step: forward_ms reads nothing instead."""
    mod = cells.load_module(cells.BENCH / "metrics" / "forward_ms.train.py",
                            "forward_ms.train")
    ops = [{"name": "fusion.1", "text": "fusion.1", "seconds": 0.3}]
    ctx = {"kind": "zo", "steps": 2, "busy_s": 0.4, "ops": ops}
    assert mod.read(ctx) is None
    ops.append({"name": "tezo_perturb.3", "text": "tezo_perturb.3",
                "seconds": 0.1})
    assert mod.read(ctx) == pytest.approx(150.0)
