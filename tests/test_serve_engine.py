"""Continuous-batching ServeEngine (PR 8): scheduler invariants under random
traces, the solo-vs-mixed bitwise contract (a request's token stream is
identical whether served alone or inserted mid-decode next to arbitrary
neighbours), the no-recompile contract (``compile_count`` frozen after
warmup), EOS evict-and-refill, per-request sampling streams, and the
legacy-BatchedServer oracle at matched capacity.

PR 10 adds speculative decoding: the prompt-lookup drafter units, the spec
engine's greedy (and temperature) streams bitwise-matching the non-spec
engine on the same churned trace — single-device and on 8 fake host
devices — multi-token commits actually landing on low-entropy workloads,
EOS truncation inside a commit, and graceful page-budget truncation
(replacing the old capacity ValueError) with queue-time stats split from
TTFT.

The real-model tests share one module-scoped engine: serve() must leave the
scheduler drained and the cache reusable, so running the solo oracles on the
*same* engine that just served the mixed trace is itself part of the test.
"""
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.serve import (
    BatchedServer,
    Request,
    ServeEngine,
    SlotScheduler,
    prompt_lookup_draft,
)
from repro.models import build_model

from _fake_mesh import run_script

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


# --------------------------------------------------------------------------
# SlotScheduler: property test over random insert/evict/decode traces
# --------------------------------------------------------------------------


def test_scheduler_random_trace_invariants():
    """400 random ops (insert / evict / simulated decode growth): after every
    one, no double-occupancy, pages disjoint and conserved, the null page
    never owned, and live_tokens() exactly the sum of resident lengths."""
    rng = np.random.default_rng(0)
    sched = SlotScheduler(n_slots=4, pages_per_slot=3, n_pages=13)
    resident: dict[str, int] = {}  # rid -> slot
    expected: dict[str, int] = {}  # rid -> length
    next_id = 0
    for _ in range(400):
        ops_avail = []
        if sched.has_free_slot():
            ops_avail.append("insert")
        if resident:
            ops_avail += ["evict", "decode"]
        op = rng.choice(ops_avail)
        if op == "insert":
            rid = f"q{next_id}"
            next_id += 1
            n = int(rng.integers(1, 3 * 4))
            slot = sched.insert(rid, n)
            assert slot not in resident.values()
            resident[rid] = slot
            expected[rid] = n
        elif op == "evict":
            rid = rng.choice(list(resident))
            got = sched.evict(resident.pop(rid))
            assert got == rid
            del expected[rid]
        else:  # a decode step grows every live sequence by one
            for rid, slot in resident.items():
                sched.lengths[slot] += 1
                expected[rid] += 1
        sched.check_invariants()
        assert sched.live_tokens() == sum(expected.values())
    # drain completely: every page returns, every slot frees
    for rid in list(resident):
        sched.evict(resident.pop(rid))
    sched.check_invariants()
    assert sched.occupied() == []
    assert sched.live_tokens() == 0


def test_scheduler_rejects_misuse():
    sched = SlotScheduler(n_slots=2, pages_per_slot=2, n_pages=5)
    slot = sched.insert("a", 3)
    with pytest.raises(AssertionError):
        sched.insert("a", 1)  # double residency
    sched.insert("b", 1)
    with pytest.raises(AssertionError):
        sched.insert("c", 1)  # no free slot
    sched.evict(slot)
    with pytest.raises(AssertionError):
        sched.evict(slot)  # already free


def test_scheduler_tables_shuffle_after_churn():
    """FIFO page recycling: after churn the block table is not the identity
    layout, so the paged tests genuinely exercise table indirection."""
    sched = SlotScheduler(n_slots=2, pages_per_slot=2, n_pages=7)
    s0 = sched.insert("a", 1)
    sched.insert("b", 1)
    sched.evict(s0)
    sched.insert("c", 1)  # FIFO hands out the never-used tail pages first
    sched.check_invariants()
    assert [int(p) for p in sched.block_tables[s0]] == [5, 6]


# --------------------------------------------------------------------------
# ServeEngine on a real smoke model
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("opt-125m")


@pytest.fixture(scope="module")
def params(cfg):
    return build_model(cfg).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine(cfg, params):
    eng = ServeEngine(
        cfg,
        params,
        max_concurrent_decodes=3,
        max_prompt_len=16,
        max_new_tokens=8,
        page_size=8,
    )
    eng.warmup()
    return eng


def _prompts(cfg):
    rng = np.random.default_rng(0)
    return [
        rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
        for n in (5, 8, 13, 16, 3, 11)
    ]


@pytest.fixture(scope="module")
def mixed(engine, cfg):
    """One staggered mixed trace, shared by the assertions below.  Arrivals
    force the full life cycle: r0–r2 fill every slot at step 0, r3 queues
    until r0's eviction frees a slot (a genuine mid-decode insertion), r4/r5
    refill later evictions."""
    prompts = _prompts(cfg)
    warm_compiles = engine.compile_count
    reqs = [
        Request(id=f"r{i}", tokens=p, max_new=6, arrival=a)
        for i, (p, a) in enumerate(zip(prompts, [0, 0, 0, 1, 6, 9]))
    ]
    results, stats = engine.serve(reqs, step_clock=True)
    return prompts, results, stats, warm_compiles


def test_no_recompile_after_warmup(engine, mixed):
    """The jit-cache-miss counter is frozen by warmup(): serving a workload
    with every prompt bucket, insertion, eviction and refill compiles
    nothing new."""
    _, _, stats, warm_compiles = mixed
    assert stats["compile_count"] == warm_compiles
    assert engine.compile_count == warm_compiles


def test_mixed_trace_accounting_and_stats(engine, mixed):
    prompts, results, stats, _ = mixed
    assert stats["requests"] == 6
    # exact live-token accounting: every request ran its full max_new budget
    assert stats["emitted_tokens"] == 6 * 6
    assert stats["live_tokens"] == 6 * 6
    assert stats["live_tokens"] == sum(len(r["tokens"]) for r in results.values())
    for key in (
        "tok_per_s",
        "ttft_p50_ms",
        "ttft_p99_ms",
        "decode_steps",
        "max_concurrent_decodes",
    ):
        assert key in stats, key
    assert stats["max_concurrent_decodes"] == 3
    # r3 arrived at step 1 but had to wait for a slot: queueing shows in TTFT
    assert results["r3"]["ttft_s"] > 0
    # the detokenize worker drained the full backlog, in emission order
    for r in results.values():
        assert r["text"] == "".join(f"<{t}>" for t in r["tokens"])
        assert r["times"] == sorted(r["times"])
    # serve() leaves the engine drained and reusable
    assert engine.scheduler.occupied() == []
    engine.scheduler.check_invariants()


def test_solo_vs_mixed_bitwise(engine, cfg, mixed):
    """THE engine contract: each request's greedy stream served solo — on
    the same engine, after the mixed trace churned the page pool — is
    bitwise the stream it got mid-flight next to its neighbours."""
    prompts, results, _, warm_compiles = mixed
    for i, p in enumerate(prompts):
        solo, _ = engine.serve(
            [Request(id=f"solo{i}", tokens=p, max_new=6)], step_clock=True
        )
        np.testing.assert_array_equal(
            solo[f"solo{i}"]["tokens"],
            results[f"r{i}"]["tokens"],
            err_msg=f"r{i} diverged between solo and mixed serving",
        )
    assert engine.compile_count == warm_compiles  # solo reruns recompile nothing


def test_eos_evicts_and_refills(engine, cfg, mixed):
    """With an EOS id picked from the no-EOS streams: every request's stream
    is exactly its no-EOS stream truncated after the first EOS, eviction
    frees slots for queued requests, and the pool drains clean."""
    prompts, results, _, _ = mixed
    eos = int(results["r0"]["tokens"][2])  # r0 stops after 3 tokens
    old = engine.eos_id
    engine.eos_id = eos  # host-side check only — never traced, no recompile
    try:
        reqs = [
            Request(id=f"e{i}", tokens=p, max_new=6) for i, p in enumerate(prompts)
        ]
        res_eos, stats = engine.serve(reqs, step_clock=True)
    finally:
        engine.eos_id = old
    assert len(res_eos) == 6  # all admitted despite 3 slots: evict → refill
    assert any(len(r["tokens"]) < 6 for r in res_eos.values())
    for i in range(6):
        full = results[f"r{i}"]["tokens"]
        hits = np.flatnonzero(full == eos)
        want = full[: hits[0] + 1] if hits.size else full
        np.testing.assert_array_equal(res_eos[f"e{i}"]["tokens"], want)
    assert stats["live_tokens"] == sum(len(r["tokens"]) for r in res_eos.values())
    assert engine.scheduler.occupied() == []
    engine.scheduler.check_invariants()


def test_temperature_stream_is_per_request(cfg, params):
    """Sampling keys off each request's own fold-in stream: temperature
    decode is deterministic for a fixed seed AND invariant to neighbours —
    the bitwise contract survives temperature > 0."""
    def run(reqs):
        eng = ServeEngine(
            cfg,
            params,
            max_concurrent_decodes=2,
            max_prompt_len=8,
            max_new_tokens=6,
            page_size=8,
            temperature=0.8,
        )
        res, _ = eng.serve(reqs, step_clock=True)
        return res

    prompts = _prompts(cfg)[:3]

    def mk(i, **kw):
        return Request(id=f"t{i}", tokens=prompts[i][:8], max_new=5, seed=100 + i, **kw)

    mixed = run([mk(0), mk(1, arrival=1), mk(2, arrival=2)])
    mixed2 = run([mk(0), mk(1, arrival=1), mk(2, arrival=2)])
    for i in range(3):
        want = mixed[f"t{i}"]["tokens"]
        np.testing.assert_array_equal(mixed2[f"t{i}"]["tokens"], want)
        solo = run([mk(i)])
        np.testing.assert_array_equal(solo[f"t{i}"]["tokens"], want)


def test_engine_matches_batched_server_oracle(engine, cfg, params):
    """At matched capacity (solo max_len == pages_per_slot * page_size) and
    a bucket-exact prompt, the paged engine reproduces the legacy dense
    BatchedServer token for token."""
    prompt = _prompts(cfg)[3]  # length 16 == the largest bucket
    assert len(prompt) == 16
    res, _ = engine.serve([Request(id="o", tokens=prompt, max_new=8)], step_clock=True)
    srv = BatchedServer(cfg, params, max_len=engine.capacity)
    tokens, stats = srv.generate(prompt[None], max_new_tokens=8)
    np.testing.assert_array_equal(res["o"]["tokens"], tokens[0])
    assert "ttft_s" in stats


def test_rejects_oversized_work(engine, cfg):
    """Oversized *prompts* still fail fast (no bucket can prefill them);
    oversized max_new no longer raises — it truncates, see the page-budget
    test below."""
    with pytest.raises(ValueError, match="exceeds"):
        engine.serve(
            [Request(id="big", tokens=np.zeros(17, np.int32), max_new=8)],
            step_clock=True,
        )
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(get_smoke_config("xlstm-350m"))


def test_page_budget_truncation(engine, cfg, mixed):
    """A request whose max_new overruns its slot's page quota is admitted,
    truncated to ``capacity - n + 1`` emissions (the final token needs no KV
    slot), flagged in its result and in stats — and the neighbour sharing
    the engine is bitwise unaffected.  The old behaviour was a ValueError;
    the block table must never be indexed past its end either way."""
    prompts, results, _, warm = mixed
    assert engine.capacity == 24
    big = Request(id="big", tokens=prompts[3], max_new=20)  # 16 + 20 > 24
    normal = Request(id="n0", tokens=prompts[0], max_new=6)
    res, stats = engine.serve([big, normal], step_clock=True)
    assert res["big"]["truncated"] is True
    assert len(res["big"]["tokens"]) == engine.capacity - 16 + 1  # 9, not 20
    assert res["n0"]["truncated"] is False
    np.testing.assert_array_equal(res["n0"]["tokens"], results["r0"]["tokens"])
    assert stats["truncated_requests"] == 1
    assert engine.compile_count == warm  # truncation is host math only
    assert engine.scheduler.occupied() == []
    engine.scheduler.check_invariants()


def test_queue_time_split_from_ttft(engine, cfg, mixed):
    """Wall-clock serve: queue_time_s (arrival → admission) is recorded
    separately from ttft_s (arrival → first token), which additionally pays
    prefill + first sample; stats carry percentiles of both."""
    prompts, _, _, _ = mixed
    reqs = [
        Request(id=f"q{i}", tokens=p, max_new=3)
        for i, p in enumerate(prompts[:2])
    ]
    res, stats = engine.serve(reqs)
    for r in res.values():
        assert r["queue_time_s"] >= 0.0
        assert r["ttft_s"] > r["queue_time_s"]
    for key in ("queue_p50_ms", "queue_p99_ms", "ttft_p50_ms", "ttft_p99_ms"):
        assert key in stats, key
    assert stats["queue_p50_ms"] <= stats["ttft_p50_ms"]


# --------------------------------------------------------------------------
# speculative decoding: drafter units + the spec==non-spec identity contract
# --------------------------------------------------------------------------


def test_prompt_lookup_draft_units():
    # longest n-gram wins, continuation follows the earlier occurrence
    assert prompt_lookup_draft([1, 2, 3, 9, 1, 2, 3], 2) == [9, 1]
    # most recent earlier occurrence is preferred
    assert prompt_lookup_draft([1, 2, 5, 1, 2, 6, 1, 2], 1) == [6]
    # falls back through shorter n-grams
    assert prompt_lookup_draft([5, 1, 9, 2, 7, 2], 3) == [7, 2]
    # proposal is capped by what follows, then by draft_len
    assert prompt_lookup_draft([1, 2, 3, 1], 10) == [2, 3, 1]
    assert prompt_lookup_draft([1, 2, 3, 1], 2) == [2, 3]
    # nothing repeats / degenerate histories → no proposal
    assert prompt_lookup_draft([1, 2, 3, 4], 3) == []
    assert prompt_lookup_draft([7], 4) == []
    assert prompt_lookup_draft([1, 2, 1, 2], 0) == []


@pytest.fixture(scope="module")
def spec_engine(cfg, params):
    eng = ServeEngine(
        cfg,
        params,
        max_concurrent_decodes=3,
        max_prompt_len=16,
        max_new_tokens=8,
        page_size=8,
        spec_decode=True,
        draft_len=4,
    )
    eng.warmup()
    return eng


def test_spec_greedy_bitwise_vs_nonspec(spec_engine, cfg, mixed):
    """ACCEPTANCE: the spec engine's greedy streams on the staggered mixed
    trace (slot churn, mid-decode insertion, queueing) are token-bitwise
    the non-spec engine's, request for request — speculation may only
    change *when* tokens appear, never *which*."""
    prompts, results, base_stats, _ = mixed
    warm = spec_engine.compile_count
    reqs = [
        Request(id=f"r{i}", tokens=p, max_new=6, arrival=a)
        for i, (p, a) in enumerate(zip(prompts, [0, 0, 0, 1, 6, 9]))
    ]
    spec_res, stats = spec_engine.serve(reqs, step_clock=True)
    assert stats["compile_count"] == warm  # spec adds exactly 0 mid-serve
    assert stats["spec_decode"] is True
    assert stats["draft_len"] == 4
    for i in range(6):
        np.testing.assert_array_equal(
            spec_res[f"r{i}"]["tokens"],
            results[f"r{i}"]["tokens"],
            err_msg=f"r{i} diverged between spec and non-spec serving",
        )
    # a verify step commits >= 1 token per live slot, so speculation can
    # only shrink the step count
    assert stats["decode_steps"] <= base_stats["decode_steps"]
    assert spec_engine.scheduler.occupied() == []
    spec_engine.scheduler.check_invariants()


def test_spec_commits_multi_token_steps(engine, spec_engine, cfg):
    """On a low-entropy workload the prompt-lookup drafter actually lands:
    drafts are proposed AND accepted (multi-token commits), and the streams
    still match the non-spec engine bitwise."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 6, size=n).astype(np.int32) for n in (9, 12, 6, 14)]
    reqs = lambda tag: [  # noqa: E731 - two identical request lists
        Request(id=f"{tag}{i}", tokens=p, max_new=8) for i, p in enumerate(prompts)
    ]
    base_res, base_stats = engine.serve(reqs("b"), step_clock=True)
    spec_res, spec_stats = spec_engine.serve(reqs("s"), step_clock=True)
    assert spec_stats["proposed_tokens"] > 0
    assert spec_stats["accepted_tokens"] > 0, spec_stats
    assert spec_stats["decode_steps"] < base_stats["decode_steps"]
    assert 0.0 < spec_stats["acceptance_rate"] <= 1.0
    assert spec_stats["tok_per_verify"] > 1.0
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            spec_res[f"s{i}"]["tokens"], base_res[f"b{i}"]["tokens"]
        )


def test_spec_temperature_replay(cfg, params):
    """Under temperature the verify-sample consumes the request's fold-in
    key per *emitted position*, so the spec stream replays the vanilla
    sampled stream bit-for-bit."""
    def run(spec):
        eng = ServeEngine(
            cfg,
            params,
            max_concurrent_decodes=2,
            max_prompt_len=8,
            max_new_tokens=6,
            page_size=8,
            temperature=0.8,
            spec_decode=spec,
            draft_len=3,
        )
        rng = np.random.default_rng(5)
        reqs = [
            Request(
                id=f"t{i}",
                tokens=rng.integers(2, 6, size=6).astype(np.int32),
                max_new=5,
                seed=200 + i,
                arrival=float(i),
            )
            for i in range(3)
        ]
        res, _ = eng.serve(reqs, step_clock=True)
        return res

    base, spec = run(False), run(True)
    for i in range(3):
        np.testing.assert_array_equal(
            spec[f"t{i}"]["tokens"], base[f"t{i}"]["tokens"]
        )


def test_spec_eos_and_truncation(spec_engine, engine, cfg, mixed):
    """EOS truncates a multi-token commit at the first EOS (matching the
    non-spec engine), and a page-budget-truncated request under speculation
    matches the non-spec truncated stream."""
    prompts, results, _, _ = mixed
    eos = int(results["r0"]["tokens"][2])
    for eng in (engine, spec_engine):
        old = eng.eos_id
        eng.eos_id = eos
    try:
        reqs = lambda tag: [  # noqa: E731
            Request(id=f"{tag}{i}", tokens=p, max_new=6)
            for i, p in enumerate(prompts)
        ]
        base_res, _ = engine.serve(reqs("b"), step_clock=True)
        spec_res, _ = spec_engine.serve(reqs("s"), step_clock=True)
    finally:
        for eng in (engine, spec_engine):
            eng.eos_id = -1
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            spec_res[f"s{i}"]["tokens"], base_res[f"b{i}"]["tokens"]
        )
    big = Request(id="big", tokens=prompts[3], max_new=20)
    res_s, stats_s = spec_engine.serve([big], step_clock=True)
    assert res_s["big"]["truncated"] is True
    assert len(res_s["big"]["tokens"]) == spec_engine.capacity - 16 + 1
    assert stats_s["truncated_requests"] == 1
    spec_engine.scheduler.check_invariants()


# --------------------------------------------------------------------------
# 8 fake host devices: the acceptance-criteria trace in a subprocess
# --------------------------------------------------------------------------

_SCRIPT = textwrap.dedent(
    """
    import numpy as np
    import jax
    from repro.configs import get_smoke_config
    from repro.launch.serve import Request, ServeEngine

    assert jax.device_count() == 8
    cfg = get_smoke_config("opt-125m")
    eng = ServeEngine(cfg, max_concurrent_decodes=4, max_prompt_len=16,
                      max_new_tokens=8, page_size=8)
    eng.warmup()
    warm = eng.compile_count

    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 16, 9, 12, 3, 14, 7, 16)]
    # 8 overlapping requests over 4 slots; late arrivals insert mid-decode
    reqs = [Request(id=f"r{i}", tokens=p, max_new=6, arrival=float(i))
            for i, p in enumerate(prompts)]
    res, stats = eng.serve(reqs, step_clock=True)
    assert stats["compile_count"] == warm, (stats["compile_count"], warm)
    assert stats["live_tokens"] == 8 * 6, stats
    # the mid-decode-inserted request r5 must be bitwise its solo run
    for i in (0, 5, 7):
        solo, _ = eng.serve([Request(id=f"s{i}", tokens=prompts[i], max_new=6)],
                            step_clock=True)
        np.testing.assert_array_equal(solo[f"s{i}"]["tokens"],
                                      res[f"r{i}"]["tokens"])
    assert eng.compile_count == warm

    # ACCEPTANCE: the speculative engine reproduces the same churned trace
    # token-bitwise on the 8-device host platform too
    spec = ServeEngine(cfg, max_concurrent_decodes=4, max_prompt_len=16,
                       max_new_tokens=8, page_size=8,
                       spec_decode=True, draft_len=4)
    spec.warmup()
    swarm = spec.compile_count
    sres, sstats = spec.serve(
        [Request(id=f"r{i}", tokens=p, max_new=6, arrival=float(i))
         for i, p in enumerate(prompts)], step_clock=True)
    assert sstats["compile_count"] == swarm, (sstats["compile_count"], swarm)
    for i in range(8):
        np.testing.assert_array_equal(sres[f"r{i}"]["tokens"],
                                      res[f"r{i}"]["tokens"])
    print("ENGINE_8DEV_OK")
    """
)


@pytest.mark.slow
def test_engine_staggered_8_fake_devices(tmp_path):
    run_script(tmp_path, "engine_8dev.py", _SCRIPT, ("ENGINE_8DEV_OK",), timeout=60)
