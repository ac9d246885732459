"""Device time by the program's own named scopes.

The program marks its phases with ``jax.named_scope``: the ZO step's
``zo.*`` scopes (``repro/core/zo_step.py``) and the forward's ``model.*``
scopes (``repro/models/transformer.py``), each name a module-level
constant there.  A scope is metadata of the compiled step: every HLO
instruction's ``op_name`` holds the path of scopes it was traced under
(``jit(step_fn)/zo.flip/vmap(jit(tezo_perturb))/pallas_call``).  The
profiler's device operations carry only the instruction's name, so the
step's optimized HLO maps each name to the innermost program scope in its
``op_name`` (with the rules of ``scope_map`` for instructions that carry
none), and the trace's
per-operation self times (``trace.reduce``'s ``ops``: clipped to the
window) are summed by scope.  Every operation lands in one scope or in
``UNSCOPED``, so the groups partition the busy time.

The map comes from the cell's step lowered from abstract shapes and
compiled again, after the window, with the metadata in the compile cache's
key: an executable cached under a key without it may carry another build's
metadata.  A program that names no scopes (one that predates them) maps
every operation to ``UNSCOPED`` with no compile.
"""
from __future__ import annotations

import re
from collections import defaultdict

UNSCOPED = "unscoped"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+) = ")
_CALLED = re.compile(r"\b(calls|to_apply|body|condition|branch_computations)="
                     r"(\{[^}]*\}|%?[^\s,}]+)")
_REF = re.compile(r"%([^\s,(){}]+)")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def program_groups() -> dict | None:
    """Each metric group's scope names, as the program declares them; None
    where the program names no scopes."""
    from repro.core import zo_step as Z
    from repro.models import transformer as T

    try:
        return {"perturb": (Z.SCOPE_BEGIN, Z.SCOPE_PERTURB, Z.SCOPE_FLIP),
                "update": (Z.SCOPE_UPDATE,),
                "attn": (T.SCOPE_ATTN,),
                "ffn": (T.SCOPE_FFN,),
                "head": (T.SCOPE_EMBED, T.SCOPE_HEAD)}
    except AttributeError:
        return None


def innermost(op_name: str, names) -> str | None:
    """The last component of an ``op_name`` path that is a program scope."""
    for part in reversed(op_name.split("/")):
        if part in names:
            return part
    return None


def scope_map(hlo: str, names) -> dict:
    """Instruction name -> its program scope (or None), for every
    instruction of an optimized HLO module's text.  An instruction takes
    the innermost program scope of its own ``op_name``; a fusion with none
    takes its fused computation's root's.  One still without a scope takes,
    in turn until nothing changes, that of the instruction calling its
    computation (a ``while`` body, a ``conditional`` branch), or else the
    one scope that all its users share: a slice or copy made only for one
    scope's use is that scope's work.  The rest (the step's scalar
    arithmetic between scopes, copies into its outputs) stay None."""
    own, fused, roots, comp_of, callers = {}, {}, {}, {}, {}
    users = {}
    comp = None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, body = m.group(2), line[m.end():]
        comp_of[name] = comp
        meta = _OP_NAME.search(body)
        own[name] = innermost(meta.group(1), names) if meta else None
        if m.group(1):
            roots[comp] = name
        called = set()
        for kind, ref in _CALLED.findall(body):
            for c in _REF.findall(ref) or [ref]:
                called.add(c)
                if kind == "calls" and " fusion(" in body:
                    fused[name] = c
                else:
                    callers.setdefault(c, []).append(name)
        for ref in _REF.findall(body.split(", metadata=", 1)[0]):
            if ref not in called and ref != name:
                users.setdefault(ref, set()).add(name)
    scope = {}
    for name, s in own.items():
        if s is None and name in fused:
            s = own.get(roots.get(fused[name]))
        scope[name] = s
    changed = True
    while changed:
        changed = False
        for name, s in scope.items():
            if s is not None:
                continue
            found = {scope.get(c) for c in callers.get(comp_of[name], ())}
            if len(found) != 1 or None in found:
                found = {scope.get(u) for u in users.get(name, ())
                         if comp_of.get(u) == comp_of[name]}
            if len(found) == 1 and None not in found:
                scope[name] = found.pop()
                changed = True
    return scope


def step_hlo(model: dict, traffic: dict) -> str:
    """The optimized HLO text of the cell's ZO step, as the zo driver
    builds and jits it, compiled with the metadata in the cache key."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from bench.drivers import zo

    cell = SimpleNamespace(config={"name": "scopes", "model": model},
                           model=model, traffic=traffic)
    prog = zo.Program(cell)
    state = jax.eval_shape(lambda: prog.init(0))
    tok = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]), jnp.int32)
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return prog.step.lower(state, {"tokens": tok, "targets": tok}
                               ).compile().as_text()
    finally:
        jax.config.update(key, before)


def scope_seconds(ctx: dict) -> dict:
    """Device seconds in the window by program scope (and ``UNSCOPED``),
    kept in ``ctx["scopes"]`` for the other readers.  The instruction map
    is ``ctx["scope_map"]`` where the caller gives one, else the step's."""
    if "scopes" not in ctx:
        groups = program_groups()
        smap = ctx.get("scope_map")
        if smap is None:
            smap = {} if groups is None else scope_map(
                step_hlo(ctx["model"], ctx["traffic"]),
                {n for names in groups.values() for n in names})
        sums = defaultdict(float)
        for o in ctx["ops"]:
            sums[smap.get(o["name"]) or UNSCOPED] += o["seconds"]
        ctx["scopes"] = dict(sums)
    return ctx["scopes"]


def group_ms(ctx: dict, group: str) -> float | None:
    """Device milliseconds per step in one metric group's scopes.  None
    where the program names these scopes and they read nothing (a scope
    lost from the program stops the traced run); 0.0 where the program
    names no scopes at all, so that everything is unscoped."""
    if ctx.get("kind") != "zo" or ctx["steps"] <= 0:
        return None
    groups = program_groups()
    if groups is None:
        return 0.0
    sums = scope_seconds(ctx)
    s = sum(sums.get(n, 0.0) for n in groups[group])
    return 1e3 * s / ctx["steps"] if s > 0 else None


def unscoped_ms(ctx: dict) -> float | None:
    """Device milliseconds per step under no program scope (0.0 when every
    operation is covered)."""
    if ctx.get("kind") != "zo" or ctx["steps"] <= 0:
        return None
    return 1e3 * scope_seconds(ctx).get(UNSCOPED, 0.0) / ctx["steps"]
