"""Driver of the serving cells: the program's ``ServeEngine`` (paged KV,
continuous batching, greedy) under an open loop of Poisson arrivals.

Traffic (a ``serve`` traffic file): ``rate_per_s`` is the offered load,
a number measured on the chip (0.8 x the knee of a rate sweep), never a
guess: a file without it is refused;
prompt and output lengths are lognormal (``median``, ``sigma``) clipped
to [``min``, ``max``].  The set of sizes and inter-arrival gaps comes
from the file's ``shape_seed``, so every run offers the same work; the
run's seed permutes which request gets which size and gap, and draws the
prompt tokens.  No request stops early (no EOS), so every request emits
exactly its output length.

Set-up makes the weights in one jitted call, builds the engine, compiles
every executable (``warmup``) and serves one request per prefill bucket,
so every program has run once before the window.  The window is one
``ServeEngine.serve`` over the requests due in ``--seconds``, to the last
token: each request is timed from the moment it was due.

Correctness: after the window and with the engine freed, a sample of the
finished requests drawn from the seed (the longest among them, and at
least ``check_tokens`` served tokens) is run through the plain reference,
teacher-forced over prompt and served tokens; ``logit_gap`` is the widest
gap by which a served token's reference logit lies below the reference's
best at that position.
"""
from __future__ import annotations

import gc
import time

import numpy as np


def _imports():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def model_config(cell):
    from repro.configs.base import ModelConfig

    return ModelConfig(name=cell.config["name"],
                       kernel_mode=cell.traffic["kernel_mode"], **cell.model)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def make_requests(m: dict, t: dict, seed: int, seconds: float) -> list:
    """(arrival s, prompt tokens, output length) for every request due in
    the window."""
    from bench.cell import CellError
    from bench.weights import seed31

    if "rate_per_s" not in t:
        raise CellError("serve traffic has no rate_per_s: sweep the knee "
                        "on the chip and write 0.8 x knee into the file")
    shape = np.random.default_rng(t["shape_seed"])
    n = max(1, int(round(t["rate_per_s"] * seconds)))
    gaps = shape.exponential(1.0 / t["rate_per_s"], size=n)
    prompts = _lengths(shape, t["prompt"], n)
    outputs = _lengths(shape, t["output"], n)
    rng = np.random.default_rng(seed31(seed, "traffic"))
    gaps = gaps[rng.permutation(n)]
    order = rng.permutation(n)
    arrivals = np.cumsum(gaps) - gaps[0]
    arrivals *= min(1.0, seconds / max(arrivals[-1] + gaps[-1], 1e-9))
    out = []
    for i in range(n):
        toks = rng.integers(0, m["vocab_size"], size=prompts[order[i]],
                            dtype=np.int32)
        out.append((float(arrivals[i]), toks, int(outputs[order[i]])))
    return out


class Program:
    """The engine under test.  ``fault`` "altered_token" changes the
    token the engine samples for the first slot at every decode step."""

    def __init__(self, cell, seed: int, fault: str | None = None):
        jax, _ = _imports()
        from repro.launch.serve import ServeEngine

        from bench import weights as W

        t = cell.traffic
        m = cell.model
        params = jax.jit(lambda k: W.make_params(m, k))(W.weights_key(seed))
        self.engine = ServeEngine(
            model_config(cell), params,
            max_concurrent_decodes=t["slots"],
            max_prompt_len=t["max_prompt_len"],
            max_new_tokens=t["max_new_tokens"], page_size=t["page_size"],
            eos_id=-1, temperature=0.0, seed=0,
        )
        self.engine.warmup()
        if fault == "altered_token":
            S, V = t["slots"], m["vocab_size"]
            inner = self.engine._sample_exe[S]

            def altered(logits, keys, steps):
                toks = np.array(inner(logits, keys, steps))
                toks[0] = (toks[0] + 1) % V
                return toks

            self.engine._sample_exe[S] = altered
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")

    def warm(self):
        """Serve one request per prefill bucket: every executable runs."""
        from repro.launch.serve import Request

        e = self.engine
        reqs = [Request(id=f"warm{b}", tokens=np.ones(b, np.int32),
                        max_new=2, arrival=0.0) for b in e.buckets]
        e.serve(reqs)

    def serve(self, requests):
        from repro.launch.serve import Request

        reqs = [Request(id=str(i), tokens=toks, max_new=n_out, arrival=a)
                for i, (a, toks, n_out) in enumerate(requests)]
        return self.engine.serve(reqs)


def check_sample(requests, results, seed: int, min_tokens: int) -> list:
    """Indices of the requests the reference checks: the longest, then a
    draw from the seed until ``min_tokens`` served tokens are covered."""
    from bench.weights import seed31

    served = {i: len(results[str(i)]["tokens"]) for i in range(len(requests))}
    longest = max(served, key=lambda i: len(requests[i][1]) + served[i])
    picked, total = [longest], served[longest]
    rng = np.random.default_rng(seed31(seed, "check"))
    for i in rng.permutation(len(requests)):
        if total >= min_tokens:
            break
        if int(i) not in picked:
            picked.append(int(i))
            total += served[int(i)]
    return picked


def reference_gaps(cell, seed: int, seqs, precision_pick: str | None = None):
    """Per sequence (prompt, served tokens): the reference's logits at each
    served position, and the gap below its best of the served token (or,
    with ``precision_pick``, of the token that precision's forward puts
    first)."""
    jax, jnp = _imports()
    from bench import weights as W
    from bench.reference import transformer as T

    m = cell.model
    key = W.weights_key(seed)
    gen_layer = jax.jit(lambda k, l: W.layer_params(m, k, l))
    outer = jax.jit(lambda k: W.outer_params(m, k))(key)
    layers = [gen_layer(key, l) for l in range(m["n_layers"])]
    ln1 = jnp.stack([p.pop("ln1") for p in layers])
    ln2 = jnp.stack([p.pop("ln2") for p in layers])
    widest = 0.0
    count = 0
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)[None]
        n = len(prompt)
        pos = slice(n - 1, n - 1 + len(served))

        def logits(precision):
            x = T.hidden(m, layers, ln1, ln2, outer["embed"], toks, precision)
            return T.head_logits(x[0, pos], outer["final_norm"],
                                 outer["lm_head"], eps=m["norm_eps"],
                                 precision=precision)

        ref = logits("f32")
        pick = (jnp.asarray(served) if precision_pick is None
                else jnp.argmax(logits(precision_pick), axis=-1))
        got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        gap = float(jnp.max(jnp.max(ref, axis=-1) - got))
        widest = max(widest, gap)
        count += len(served)
    return widest, count


def readings(cell, seed: int, seconds: float, mode: str = "program"):
    """(widest logit gap, tokens compared) for one seed after a short
    window; ``mode`` "control" reads the fp8 forward's first choices at
    the program's positions instead of the program's tokens."""
    t = cell.traffic
    prog = Program(cell, seed, None if mode in ("program", "control")
                   else mode)
    prog.warm()
    requests = make_requests(cell.model, t, seed, seconds)
    results, _ = prog.serve(requests)
    del prog
    gc.collect()
    idx = check_sample(requests, results, seed, t["check_tokens"])
    seqs = [(requests[i][1], results[str(i)]["tokens"]) for i in idx]
    return reference_gaps(cell, seed, seqs,
                          "fp8" if mode == "control" else None)


def _p(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        fault: str | None = None) -> dict:
    jax, _ = _imports()
    from bench import harness

    clock = harness.CompileClock()
    t, m = cell.traffic, cell.model
    requests = make_requests(m, t, seed, seconds)
    prog = Program(cell, seed, fault)
    t_built = time.perf_counter()
    prog.warm()
    setup_s = time.perf_counter() - t_start
    parts = {"compile_s": clock.total, "to_engine_s": t_built - t_start,
             "warm_s": time.perf_counter() - t_built}
    compiles_before = clock.count
    if trace:
        with harness.traced_window() as tw:
            results, stats = prog.serve(requests)
    else:
        results, stats = prog.serve(requests)
    compiled_in_window = clock.count - compiles_before
    device = harness.device_record(cell.chips)
    ttft = [r["ttft_s"] for r in results.values()]
    itl = [g for r in results.values() for g in np.diff(r["times"])]
    served = sum(len(r["tokens"]) for r in results.values())
    failed = sum(len(results.get(str(i), {"tokens": []})["tokens"]) != n_out
                 for i, (_, _, n_out) in enumerate(requests))

    per_layer = breakdown = None
    if trace:
        from bench import cell as cells
        from bench import counts
        from bench import trace as TR

        red = TR.reduce(TR.load(tw["xplane"])) if tw.get("xplane") else \
            TR.reduce(TR.Trace())
        harness.drop_trace(tw)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {
            "kind": "serve", "host_window_s": stats["wall_s"],
            "busy_s": red["busy_s"], "window_s": red["window_s"],
            "ops": red["ops"], "modules": red["modules"], "model": m, "traffic": t,
            "requests": [(len(toks), len(results[str(i)]["tokens"]))
                         for i, (_, toks, _) in enumerate(requests)],
            "peak": harness.peaks(device["kind"]), "counts": counts,
        }
        per_layer = cells.read_per_layer(cell, ctx)
        breakdown = red["breakdown"]

    idx = check_sample(requests, results, seed, t["check_tokens"])
    seqs = [(requests[i][1], results[str(i)]["tokens"]) for i in idx]
    del prog
    gc.collect()
    t_ref = time.perf_counter()
    gap, n_checked = reference_gaps(cell, seed, seqs)
    parts["reference_s"] = time.perf_counter() - t_ref
    checks = {
        "logit_gap": {"value": gap, "limit": cell.limits["logit_gap"]},
        "tokens_short": {"value": max(0, t["check_tokens"] - n_checked),
                         "limit": 0},
        "compiles_in_window": {"value": compiled_in_window, "limit": 0},
    }
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    if trace:
        metrics = per_layer
    else:
        metrics = {
            "ttft_p95_ms": {"value": 1e3 * _p(ttft, 95), "unit": "ms"},
            "itl_p95_ms": {"value": 1e3 * _p(itl, 95), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {k: v for k, v in metrics.items()
                   if k in {e["name"] for e in cell.end_to_end}}
    out = {"correct": bool(correct), "attempted": len(requests),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    parts.update(served_tokens=served, window_s=stats["wall_s"],
                 queue_p50_ms=stats["queue_p50_ms"],
                 queue_p99_ms=stats["queue_p99_ms"])
    out["setup_parts"] = parts
    out["checks"] = checks
    return out
