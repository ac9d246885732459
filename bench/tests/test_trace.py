"""The trace reduction on small traces: busy union, idle share,
per-operation sums and the labels of idle gaps."""
from pathlib import Path

import pytest

from bench import kernels
from bench import trace as TR

MS = 1_000_000  # ns


def _trace():
    tr = TR.Trace()
    tr.device["/device:TPU:0"] = [
        ("fusion.1", 0 * MS, 4 * MS, "fusion.1 hlo_op=fusion.1"),
        ("tezo_perturb.2", 4 * MS, 5 * MS, "%tezo_perturb.2 = custom-call()"),
        ("fusion.1", 7 * MS, 9 * MS, "fusion.1 hlo_op=fusion.1"),
    ]
    tr.host = [
        ("bench.window", 0, 10 * MS),
        ("zo.step", 0, 6 * MS),
        ("zo.wait", 5 * MS, 10 * MS),
    ]
    return tr


def test_union_and_gaps():
    assert TR.union_length([(0, 4), (3, 5), (7, 9)]) == 7
    assert TR.gaps([(0, 4), (3, 5), (7, 9)], 0, 10) == [(5, 7), (9, 10)]
    assert TR.gaps([], 2, 3) == [(2, 3)]


def test_reduce_busy_idle_and_kernels():
    red = TR.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.007)
    by = {o["name"]: o["seconds"] for o in red["ops"]}
    assert by == pytest.approx({"fusion.1": 0.006, "tezo_perturb.2": 0.001})
    passes = TR.seconds_matching(red["ops"], kernels.matcher(kernels.ZO_PASS))
    assert passes == pytest.approx(0.001)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # the gap 5-7 ms lies in zo.wait (the innermost span at its middle)
    assert gaps == pytest.approx({"zo.wait": 0.003})


def test_nested_ops_count_their_self_time():
    tr = _trace()
    # a while loop spanning two ops of its body: 6 ms, 3 ms of them its own
    tr.device["/device:TPU:0"] = [
        ("while.1", 0, 6 * MS, "%while.1 = while()"),
        ("fusion.2", 1 * MS, 2 * MS, "%fusion.2 = fusion()"),
        ("tezo_perturb.3", 3 * MS, 5 * MS, "%tezo_perturb.3 = custom-call()"),
    ]
    red = TR.reduce(tr)
    by = {o["name"]: o["seconds"] for o in red["ops"]}
    assert by == pytest.approx(
        {"while.1": 0.003, "fusion.2": 0.001, "tezo_perturb.3": 0.002})
    assert red["busy_s"] == pytest.approx(0.006)


def test_op_and_module_names():
    assert TR.op_name("%fusion.3 = bf16[2]{0} fusion(%a)") == "fusion.3"
    assert TR.op_name("plain") == "plain"
    assert TR.module_name("jit_step_fn(123)") == "jit_step_fn"


def test_reduce_averages_over_chips():
    tr = _trace()
    tr.device["/device:TPU:1"] = [("fusion.1", 0, 10 * MS, "fusion.1")]
    red = TR.reduce(tr)
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx((0.007 + 0.010) / 2)


def test_window_falls_back_to_device_span():
    tr = _trace()
    tr.host = [("bench.window", 100 * MS, 110 * MS)]  # another clock
    assert TR.window_bounds(tr) == (0, 9 * MS)


def test_no_device_events_reads_nothing():
    red = TR.reduce(TR.Trace())
    assert red["busy_s"] == 0.0 and red["ops"] == []


RECORDED = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e: three rounds of a jitted matmul chain
    and a flash-attention kernel inside ``bench.window``, each round in a
    ``host.work`` span, with a host sleep between rounds."""
    tr = TR.load(str(RECORDED))
    assert list(tr.device) == ["/device:TPU:0"]
    red = TR.reduce(tr)
    assert 0 < red["busy_s"] < red["window_s"]
    total = sum(o["seconds"] for o in red["ops"])
    # no nesting here: the ops' self times cover the busy time
    assert total == pytest.approx(red["busy_s"], rel=1e-6)
    flash = TR.seconds_matching(red["ops"],
                                lambda name: name.startswith("flash_attention"))
    assert 0 < flash < red["busy_s"]
    assert sum(red["modules"].values()) >= 0.99 * red["busy_s"]
    assert red["breakdown"]["idle_gaps"]
