"""Device time by the program's named scopes: the HLO scope map, the six
readers that partition the busy time, and what they read when the program
names no scopes."""
import json
from pathlib import Path

import pytest

from bench import cell as cells
from bench import scopes
from bench import trace as TR

MS = 1_000_000  # ns
DATA = Path(__file__).parent / "data"
READERS = ("perturb_ms.train", "update_ms.train", "attn_ms.train",
           "ffn_ms.train", "head_ms.train", "unscoped_ms.train")
NAMES = {n for names in scopes.program_groups().values() for n in names}

HLO = """\
HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (param_0: bf16[4]) -> bf16[4] {
  %param_0 = bf16[4]{0} parameter(0)
  ROOT %add.1 = bf16[4]{0} add(%param_0, %param_0), metadata={op_name="jit(step_fn)/while/body/model.ffn/add"}
}

%body.2 (arg: (s32[], bf16[4])) -> (s32[], bf16[4]) {
  %arg = (s32[], bf16[4]{0}) parameter(0)
  %gte.1 = bf16[4]{0} get-tuple-element(%arg), index=1
  %slice.3 = bf16[4]{0} dynamic-slice(%gte.1), metadata={op_name="jit(step_fn)/while/body/dynamic_slice"}
  %dot.4 = bf16[4]{0} dot(%slice.3, %slice.3), metadata={op_name="jit(step_fn)/while/body/model.attn/dot_general"}
  %fusion.5 = bf16[4]{0} fusion(%dot.4), kind=kLoop, calls=%fused_computation.1
  ROOT %tuple.6 = (s32[], bf16[4]{0}) tuple(%gte.1, %fusion.5)
}

%branch.7 (p: bf16[4]) -> bf16[4] {
  %p = bf16[4]{0} parameter(0)
  ROOT %mul.8 = bf16[4]{0} multiply(%p, %p)
}

ENTRY %main.9 (w: bf16[4]) -> (bf16[4], f32[]) {
  %w = bf16[4]{0} parameter(0), metadata={op_name="w"}
  %perturb.10 = bf16[4]{0} custom-call(%w), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/zo.perturb/vmap(jit(tezo_perturb))/pallas_call"}
  %while.11 = (s32[], bf16[4]{0}) while(%perturb.10), condition=%cond.0, body=%body.2, metadata={op_name="jit(step_fn)/while"}
  %cond.12 = bf16[4]{0} conditional(%perturb.10), branch_computations={%branch.7, %branch.7}, metadata={op_name="jit(step_fn)/zo.update/cond"}
  %kappa.13 = f32[] divide(%w, %w), metadata={op_name="jit(step_fn)/div"}
  %copy.14 = bf16[4]{0} copy(%cond.12)
  ROOT %tuple.15 = (bf16[4]{0}, f32[]) tuple(%copy.14, %kappa.13)
}
"""


def test_scope_map_rules():
    m = scopes.scope_map(HLO, NAMES)
    assert m["perturb.10"] == "zo.perturb"       # its own op_name
    assert m["dot.4"] == "model.attn"
    assert m["fusion.5"] == "model.ffn"          # its fused root's
    assert m["slice.3"] == "model.attn"          # the scope of its one user
    assert m["mul.8"] == "zo.update"             # its caller's
    assert m["kappa.13"] is None                 # between scopes
    assert m["copy.14"] is None                  # a copy into the outputs
    assert m["while.11"] is None


def test_innermost_scope_wins():
    path = "jit(step_fn)/model.attn/PALLAS_FLASH_REGION/model.head/x"
    assert scopes.innermost(path, NAMES) == "model.head"
    assert scopes.innermost("jit(step_fn)/div", NAMES) is None


def _read(name, ctx):
    mod = cells.load_module(cells.BENCH / "metrics" / f"{name}.py", name)
    return mod.read(ctx)


def _ctx(smap):
    """Two steps of a hand-made trace: a while op around an attention and
    an FFN op, a perturb, an update, a head op and an unscoped copy."""
    tr = TR.Trace()
    tr.device["/device:TPU:0"] = [
        ("while.1", 0, 5 * MS, "%while.1 = while()"),
        ("dot.2", 0, 2 * MS, "%dot.2 = dot()"),
        ("fusion.3", 2 * MS, 4 * MS, "%fusion.3 = fusion()"),
        ("perturb.4", 5 * MS, 8 * MS, "%perturb.4 = custom-call()"),
        ("update.5", 8 * MS, 12 * MS, "%update.5 = custom-call()"),
        ("fusion.6", 12 * MS, 13 * MS, "%fusion.6 = fusion()"),
        ("copy.7", 14 * MS, 15 * MS, "%copy.7 = copy()"),
    ]
    tr.host = [("bench.window", 0, 16 * MS)]
    red = TR.reduce(tr)
    return {"kind": "zo", "steps": 2, "busy_s": red["busy_s"],
            "window_s": red["window_s"], "ops": red["ops"],
            "scope_map": smap}


SMAP = {"while.1": None, "dot.2": "model.attn", "fusion.3": "model.ffn",
        "perturb.4": "zo.flip", "update.5": "zo.update",
        "fusion.6": "model.head", "copy.7": None}


def test_six_metrics_partition_busy_time():
    ctx = _ctx(SMAP)
    got = {n: _read(n, ctx) for n in READERS}
    assert got == pytest.approx({
        "perturb_ms.train": 1.5, "update_ms.train": 2.0,
        "attn_ms.train": 1.0, "ffn_ms.train": 1.0, "head_ms.train": 0.5,
        "unscoped_ms.train": 1.0})  # the while's own 1 ms and the copy
    per_step = 1e3 * ctx["busy_s"] / ctx["steps"]
    assert sum(got.values()) == pytest.approx(per_step, rel=1e-3)


@pytest.mark.parametrize("name,gone", [
    ("perturb_ms.train", ("perturb.4",)), ("update_ms.train", ("update.5",)),
    ("attn_ms.train", ("dot.2",)), ("ffn_ms.train", ("fusion.3",)),
    ("head_ms.train", ("fusion.6",))])
def test_reader_reads_none_when_its_scopes_are_absent(name, gone):
    smap = {k: (None if k in gone else v) for k, v in SMAP.items()}
    assert _read(name, _ctx(smap)) is None


def test_unscoped_reads_zero_when_everything_is_scoped():
    smap = {k: v or "model.ffn" for k, v in SMAP.items()}
    assert _read("unscoped_ms.train", _ctx(smap)) == 0.0


def test_program_without_scopes(monkeypatch):
    """A program that names no scopes (one that predates them): every
    operation is unscoped, nothing is compiled, and no reader stops the
    run."""
    monkeypatch.setattr(scopes, "program_groups", lambda: None)
    monkeypatch.setattr(scopes, "step_hlo", pytest.fail)
    ctx = _ctx(None)
    del ctx["scope_map"]
    got = {n: _read(n, ctx) for n in READERS}
    per_step = 1e3 * ctx["busy_s"] / ctx["steps"]
    assert got.pop("unscoped_ms.train") == pytest.approx(per_step)
    assert set(got.values()) == {0.0}


def test_step_hlo_maps_the_cells_step():
    """The step the zo driver builds, compiled on the CPU at a tiny size:
    every metric group holds some of its instructions."""
    model = {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
             "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
             "vocab_size": 256, "activation": "gelu", "rope_theta": 1e4,
             "norm_eps": 1e-5, "dtype": "float32"}
    traffic = json.loads((cells.BENCH / "traffic" / "zo-short.json")
                         .read_text())
    traffic.update(kernel_mode="xla", batch=2, seq=8)
    hlo = scopes.step_hlo(model, traffic)
    smap = scopes.scope_map(hlo, NAMES)
    ctx = {"kind": "zo", "steps": 1, "model": model, "traffic": traffic,
           "ops": [{"name": n, "seconds": 1e-6} for n in smap]}
    for group in ("perturb", "update", "attn", "ffn", "head"):
        assert scopes.group_ms(ctx, group) > 0, group


def test_reduce_on_recorded_trace_is_unchanged():
    red = TR.reduce(TR.load(str(DATA / "small.xplane.pb")))
    want = json.loads((DATA / "small.reduce.json").read_text())
    assert json.loads(json.dumps(red)) == want
