"""Named scopes in the ZO step and the transformer.

The step marks its phases (``zo.begin``, ``zo.perturb``, ``zo.flip``,
``zo.update``) and the forward its parts (``model.embed``, ``model.attn``,
``model.ffn``, ``model.head``).  The benchmark maps each device operation
of a trace to a scope through the compiled step's HLO
(``bench/scopes.py``), so two contracts hold:

1. every instruction of a tiny dense TeZO step, compiled on CPU, that
   does real work lands in one of the eight scopes; left out are the
   step's scalar arithmetic between scopes (kappa, the loss metric, the
   step counter: results of at most q elements) and the copies into its
   outputs;
2. a scope adds metadata and nothing else: with ``jax.named_scope`` made
   a no-op the optimized HLO, metadata stripped, is the same text.
"""
import contextlib
import functools
import re

import jax
import numpy as np
import pytest

from bench import scopes
from repro.configs.base import ModelConfig
from repro.core import ZOConfig, build_zo_train_step, init_zo_state
from repro.core import zo_step
from repro.models import build_model, transformer

SCOPES = {zo_step.SCOPE_BEGIN, zo_step.SCOPE_PERTURB, zo_step.SCOPE_FLIP,
          zo_step.SCOPE_UPDATE, transformer.SCOPE_EMBED,
          transformer.SCOPE_ATTN, transformer.SCOPE_FFN,
          transformer.SCOPE_HEAD}
# plumbing: no device work of its own
PLUMBING = ("tuple", "get-tuple-element", "parameter", "constant", "bitcast",
            "while", "conditional", "call")
B, S = 2, 8


def _model():
    # float32: on CPU a bfloat16 model gets f32 converts of the weights
    # that the backend adds with no metadata
    return build_model(ModelConfig(
        name="tiny", kernel_mode="xla", family="dense", n_layers=2,
        d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
        vocab_size=256, activation="gelu", rope_theta=1e4, norm_eps=1e-5,
        dtype="float32"))


def _step_hlo(restore_mode="inplace", q=1, mesh=None, method="tezo_adam"):
    model = _model()
    cfg = ZOConfig(method=method, kernel_mode="xla", rank=4,
                   q_probes=q, restore_mode=restore_mode,
                   probe_parallel=mesh is not None)
    step = jax.jit(build_zo_train_step(model.loss_fn, cfg, mesh=mesh),
                   donate_argnums=0)
    state = jax.eval_shape(
        lambda: init_zo_state(model.init(jax.random.PRNGKey(0)), cfg))
    tok = jax.ShapeDtypeStruct((B, S), np.int32)
    return step.lower(state, {"tokens": tok, "targets": tok}
                      ).compile().as_text()


@functools.lru_cache(maxsize=None)
def _scoped_hlo(restore_mode="inplace", q=1):
    return _step_hlo(restore_mode, q)


def _instructions(hlo):
    """(computation, name, opcode, line) of every instruction outside the
    fused computations (the operations a device trace shows)."""
    fused = set(re.findall(r"\bcalls=%?([^\s,}]+)", "\n".join(
        line for line in hlo.splitlines() if " fusion(" in line)))
    comp, out = None, []
    for line in hlo.splitlines():
        m = scopes._COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = scopes._INSTRUCTION.match(line)
        if m and comp not in fused:
            out.append((comp, m.group(2), _opcode(line[m.end():]), line))
    return out


def _opcode(rest):
    """The opcode after an instruction's result type (a tuple type is
    parenthesised and may nest)."""
    depth, i = 0, 0
    if rest.startswith("("):
        for i, c in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(c, 0)
            if depth == 0:
                break
    rest = rest[i:].split(" ", 1)[1] if " " in rest[i:] else ""
    return rest.split("(", 1)[0]


def _outputs(hlo):
    """Names the entry computation's ROOT tuple takes."""
    entry = hlo[hlo.index("\nENTRY"):]
    root = next(line for line in entry.splitlines() if "ROOT " in line)
    return set(re.findall(r"%([^\s,(){}]+)", root.split(" = ", 1)[1]))


def _scalar(line, q):
    """A result of at most q elements: kappa and the other per-probe
    scalars."""
    shape = re.match(r"\s+(?:ROOT\s+)?%?\S+ = \w+\[([\d,]*)\]", line)
    return shape is not None and np.prod(
        [int(d) for d in shape.group(1).split(",") if d] or [1]) <= q


@pytest.mark.parametrize("restore_mode,q", [
    ("inplace", 1), ("inplace", 2), ("unchained", 1), ("exact", 1)])
def test_every_working_instruction_is_scoped(restore_mode, q):
    hlo = _scoped_hlo(restore_mode, q)
    smap = scopes.scope_map(hlo, SCOPES)
    outputs = _outputs(hlo)
    unscoped = [line.strip()[:160] for _, name, op, line in
                _instructions(hlo)
                if smap[name] is None and op not in PLUMBING
                and not _scalar(line, q) and name not in outputs]
    assert not unscoped, "\n".join(unscoped[:20])
    # TeZO has no per-step state to begin: it draws tau inside each pass
    assert set(smap.values()) - {None} == SCOPES - {zo_step.SCOPE_BEGIN}


def test_begin_scope_holds_the_subspace_refresh():
    # SubZO refreshes its orthonormal subspace in begin_step
    smap = scopes.scope_map(_step_hlo(method="subzo"), SCOPES)
    assert zo_step.SCOPE_BEGIN in set(smap.values())


def test_probe_parallel_lane_is_scoped():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    smap = scopes.scope_map(_step_hlo(q=2, mesh=mesh), SCOPES)
    assert set(smap.values()) - {None} == SCOPES - {zo_step.SCOPE_BEGIN}


def _strip(hlo):
    """The HLO text without metadata: no ``metadata={...}`` and no source
    location tables."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif line.startswith(("%", "ENTRY")):
            skip = False
        if not skip:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def test_scopes_change_metadata_only(monkeypatch):
    scoped = _scoped_hlo()
    assert "zo.flip" in scoped and "model.ffn" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _step_hlo()
    assert "zo.flip" not in bare and "model.ffn" not in bare
    assert _strip(scoped) == _strip(bare)
