"""Profiler trace -> device busy time, per-operation device time and the
idle gaps, each labelled by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes:
each TPU plane's "XLA Ops" line gives the device's operations (named by
their HLO instruction, ``%name = shape opcode(...)``; a Pallas kernel is
the custom call named after the jitted wrapper that made it, e.g.
``tezo_perturb.12`` or ``vmap_jit_tezo_adam_update__.6``), its "XLA
Modules" line the executables that ran (``jit_<function>(<hash>)``), and
every host plane's lines the host spans (``TraceAnnotation``s among
them).  A ``while`` or other control op spans the ops of its body on the
same line; per-operation sums take each op's self time, its duration
less that of the ops nested in it.  ``reduce`` works on plain tuples, so
it is tested on small hand-made traces as well as on recorded ones.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclass
class Trace:
    # plane name -> [(op name, start ns, end ns, text)]; the name is the
    # HLO instruction's, the text its whole HLO line
    device: dict = field(default_factory=dict)
    # plane name -> [(module name, start ns, end ns)]
    modules: dict = field(default_factory=dict)
    # [(span name, start ns, end ns)] of every host thread
    host: list = field(default_factory=list)


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%") if hlo.startswith("%") else hlo


def module_name(name: str) -> str:
    """``jit_step_fn(123456)`` -> ``jit_step_fn``."""
    return name.split("(", 1)[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = tr.device.setdefault(plane.name, [])
                    for e in line.events:
                        evs.append((op_name(e.name), e.start_ns, e.end_ns,
                                    e.name))
                elif line.name == MODULES_LINE:
                    mods = tr.modules.setdefault(plane.name, [])
                    for e in line.events:
                        mods.append((module_name(e.name), e.start_ns,
                                     e.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        tr.host.append((e.name, e.start_ns, e.end_ns))
    return tr


def union_length(intervals) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """Idle [start, end) stretches of [lo, hi) not covered by intervals."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def window_bounds(tr: Trace):
    """The traced window on the trace's clock: the harness's
    ``bench.window`` host span where the device operations lie inside it,
    else the span of the device operations themselves."""
    ops = [(s, e) for evs in tr.device.values() for _, s, e, _ in evs]
    if not ops:
        return None
    lo_d, hi_d = min(s for s, _ in ops), max(e for _, e in ops)
    spans = [(s, e) for n, s, e in tr.host if n == WINDOW_SPAN]
    if spans:
        lo, hi = spans[0]
        inside = sum(min(e, hi) - max(s, lo) for s, e in ops
                     if e > lo and s < hi)
        total = sum(e - s for s, e in ops)
        if total > 0 and inside >= 0.9 * total:
            return lo, max(hi, hi_d)
    return lo_d, hi_d


def _label(tr: Trace, s, e) -> str:
    """The innermost host span (other than the window) covering the
    middle of [s, e)."""
    mid = 0.5 * (s + e)
    best = None
    for n, hs, he in tr.host:
        if n == WINDOW_SPAN or not hs <= mid < he:
            continue
        if best is None or he - hs < best[1]:
            best = (n, he - hs)
    return best[0] if best else "no host span"


def self_times(evs):
    """Each event's duration less that of the events nested in it (on
    one line, events either nest or do not overlap)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    own = [e - s for _, s, e, _ in evs]
    stack = []
    for i in order:
        _, s, e, _ = evs[i]
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def reduce(tr: Trace, top: int = 10) -> dict:
    """Busy and window seconds (averaged over chips), per-operation device
    seconds, and the breakdown of the longest operations and idle gaps."""
    bounds = window_bounds(tr)
    if bounds is None:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": [], "modules": {},
                "chips": 0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    lo, hi = bounds
    n = len(tr.device)
    busy = 0.0
    per_op = defaultdict(float)
    per_module = defaultdict(float)
    all_gaps = []
    for plane, evs in tr.device.items():
        inside = [ev for ev in evs if ev[2] > lo and ev[1] < hi]
        clipped = [(max(s, lo), min(e, hi)) for _, s, e, _ in inside]
        busy += union_length(clipped)
        for (name, s, e, _), own in zip(inside, self_times(inside)):
            # an op cut by the window's edge counts its share inside it
            per_op[name] += own * (min(e, hi) - max(s, lo)) / max(e - s, 1)
        for name, s, e in tr.modules.get(plane, []):
            if e > lo and s < hi:
                per_module[name] += min(e, hi) - max(s, lo)
        all_gaps += gaps(clipped, lo, hi)
    ops = sorted(({"name": k, "text": k, "seconds": v / n * 1e-9}
                  for k, v in per_op.items()), key=lambda o: -o["seconds"])
    modules = {k: v / n * 1e-9 for k, v in per_module.items()}
    gap_by_label = defaultdict(float)
    for s, e in sorted(all_gaps, key=lambda g: g[0] - g[1])[: 50 * top]:
        gap_by_label[_label(tr, s, e)] += (e - s) / n * 1e-9
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "chips": n,
        "ops": ops,
        "modules": modules,
        "breakdown": {
            "device_ops": [[o["name"], o["seconds"]] for o in ops[:top]],
            "idle_gaps": sorted(([k, v] for k, v in gap_by_label.items()),
                                key=lambda kv: -kv[1])[:top],
        },
    }


def seconds_matching(ops, predicate) -> float:
    return sum(o["seconds"] for o in ops if predicate(o["text"]))
