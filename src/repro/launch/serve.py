"""Serving: a continuous-batching engine over a paged KV cache, plus the
legacy static-batch driver.

``ServeEngine`` is the production shape (MaxText offline-inference style):

* **Prefill buckets.** Prompts pad (after the prompt — causal masking makes
  the tail inert) to power-of-two buckets, and every bucket's prefill is
  AOT-compiled at ``warmup()`` (``jax.jit(...).lower(...).compile()``), so a
  new request shape never recompiles mid-serve.  The true prompt length is a
  traced scalar: one executable per bucket covers every length in it.
* **Slots + page table.** Decode state is persistent at
  ``max_concurrent_decodes`` slots over a shared KV page pool
  (``[L, n_pages, page_size, KV, dh]``).  Each slot owns a fixed set of
  physical pages recorded in a host-side block table; a finished prefill is
  *inserted* into a free slot (page scatter + table row), EOS/max-new
  *evicts* it (the pages return to the free list), and the next queued
  request refills the slot — no lockstep draining of a whole batch, and
  evict/insert never copies cache.  Page 0 is reserved as the null page so
  free slots' decode writes can't corrupt live pages.
* **Paged decode kernel.** Each step runs one fixed-shape
  ``decode_step_paged`` over all slots; attention goes through
  ``core.dispatch.decode_attention_fwd`` (the block-table Pallas kernel on
  TPU / interpret-under-tests, the gather-then-dense XLA twin elsewhere).
* **Speculative decoding.** With ``spec_decode=True`` each step drafts up
  to ``draft_len`` tokens per slot with a model-free prompt-lookup (n-gram)
  drafter over the request's own history, scores the whole window in one
  ``verify_step_paged`` forward (multi-token paged verify attention), and
  commits the longest agreeing prefix plus the bonus token.  Rejected draft
  KV is rolled back by the length pointer — never copied.  The greedy
  spec stream is token-bitwise identical to the non-spec engine, and under
  temperature the per-request fold-in key is consumed per *emitted
  position*, so sampling replays the vanilla stream too.
* **Threaded detokenize.** Emitted tokens go to a daemon worker through an
  unbounded queue — the decode loop never blocks on host-side
  detokenization; the backlog drains at ``finish()``.
* **No-recompile contract.** ``compile_count`` counts every XLA compile the
  engine performs; after ``warmup()`` it must not grow during ``serve()``
  (the serving tests assert exactly that).
* **Page-budget exhaustion.** A request whose ``max_new`` overruns its
  slot's page quota is admitted anyway with a truncated emission budget
  (flagged in its result and in stats) — the block table is never indexed
  past its end, and no live slot ever reaches the capacity pointer.

Every per-slot op in the decode step is row-independent, so a request's
token stream is bitwise-identical whether it is served alone or inserted
mid-decode next to arbitrary other requests (greedy, or temperature
sampling with the per-request fold-in key stream) — the engine's core
correctness contract, property-tested in tests/test_serve_engine.py.

``BatchedServer`` below is the legacy fixed-batch loop (prefill once,
decode the whole batch in lockstep, freeze rows at EOS); it remains the
oracle the engine is compared against.

    PYTHONPATH=src python -m repro.launch.serve --arch opt-125m --smoke \
        --engine --batch 8 --prompt-len 32 --max-new 16 --eos-id 1
"""

from __future__ import annotations

import argparse
import json
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model


@dataclass
class Request:
    """One serving request.  ``arrival`` is seconds since serve() start
    (wall-clock admission), or a decode-step index under ``step_clock``
    (deterministic tests); ``seed`` keys the per-request sampling stream."""

    id: str
    tokens: np.ndarray
    max_new: int = 16
    arrival: float = 0.0
    seed: int = 0


@dataclass
class _Live:
    """Host-side state of a request currently occupying a slot.

    ``budget`` is the emission budget actually granted (``req.max_new``,
    or less when the slot's page quota can't hold it — then ``truncated``
    is set); ``history`` is prompt + everything emitted so far, the
    drafter's only input (a pure function of the request's own stream, so
    speculation cannot couple slots)."""

    req: Request
    slot: int
    generated: int = 0
    key: np.ndarray = field(default_factory=lambda: np.zeros(2, np.uint32))
    budget: int = 0
    truncated: bool = False
    history: list = field(default_factory=list)


def prompt_lookup_draft(history, draft_len: int, max_ngram: int = 3) -> list:
    """Model-free prompt-lookup drafter (PLD / n-gram speculation).

    Finds the longest n-gram (n ≤ ``max_ngram``) ending the history that
    also occurred earlier, preferring the most recent earlier occurrence,
    and proposes up to ``draft_len`` of the tokens that followed it.
    Deterministic and a pure function of the request's *own* history —
    the engine's solo-vs-batched bitwise identity survives speculation.
    Returns [] when no n-gram repeats (the engine then verifies a
    1-token window, which is exactly a decode step)."""
    L = len(history)
    if L < 2 or draft_len <= 0:
        return []
    for n in range(min(max_ngram, L - 1), 0, -1):
        suffix = history[L - n :]
        for start in range(L - n - 1, -1, -1):
            if history[start : start + n] == suffix:
                return list(history[start + n : start + n + draft_len])
    return []


class SlotScheduler:
    """Host-side slot and page-table bookkeeping for the engine.

    Invariants (``check_invariants`` asserts them; the property tests drive
    random insert/evict traces against it):

    * no double-occupancy: a request id occupies at most one slot;
    * every occupied slot owns exactly ``pages_per_slot`` distinct physical
      pages, disjoint from every other slot's and from the free list;
    * free pages ∪ owned pages == {1 .. n_pages-1} (page 0 is the reserved
      null page and is never owned);
    * ``live_tokens()`` equals the sum of occupied slots' lengths, exactly.

    Pages are handed out from a FIFO free list that evictions append to, so
    long-running traces genuinely shuffle the physical layout — the block
    table is load-bearing, not an identity map.
    """

    def __init__(self, n_slots: int, pages_per_slot: int, n_pages: int):
        assert n_pages >= n_slots * pages_per_slot + 1, (
            n_pages,
            n_slots,
            pages_per_slot,
        )
        self.n_slots = n_slots
        self.pages_per_slot = pages_per_slot
        self.n_pages = n_pages
        self.block_tables = np.zeros((n_slots, pages_per_slot), np.int32)
        self.lengths = np.zeros((n_slots,), np.int32)
        self.requests: list[str | None] = [None] * n_slots
        self._free_slots: deque[int] = deque(range(n_slots))
        self._free_pages: deque[int] = deque(range(1, n_pages))

    def has_free_slot(self) -> bool:
        return bool(self._free_slots)

    def occupied(self) -> list[int]:
        return [s for s in range(self.n_slots) if self.requests[s] is not None]

    def insert(self, req_id: str, n_tokens: int) -> int:
        """Claim a free slot and its page quota for ``req_id``; returns the
        slot.  The caller scatters the prefilled KV into
        ``block_tables[slot][:n_prompt_pages]``."""
        assert self._free_slots, "insert with no free slot"
        assert req_id not in self.requests, f"{req_id} already resident"
        slot = self._free_slots.popleft()
        pages = [self._free_pages.popleft() for _ in range(self.pages_per_slot)]
        self.block_tables[slot] = pages
        self.lengths[slot] = n_tokens
        self.requests[slot] = req_id
        return slot

    def evict(self, slot: int) -> str:
        """Release a slot: its pages go back on the free list, the table row
        points at the null page.  A page-table edit — no cache copy."""
        rid = self.requests[slot]
        assert rid is not None, f"evict of free slot {slot}"
        self._free_pages.extend(int(p) for p in self.block_tables[slot])
        self.block_tables[slot] = 0
        self.lengths[slot] = 0
        self.requests[slot] = None
        self._free_slots.append(slot)
        return rid

    def live_tokens(self) -> int:
        return int(self.lengths.sum())

    def check_invariants(self) -> None:
        occ = self.occupied()
        rids = [self.requests[s] for s in occ]
        assert len(rids) == len(set(rids)), f"double-occupancy: {rids}"
        owned: list[int] = []
        for s in range(self.n_slots):
            row = [int(p) for p in self.block_tables[s]]
            if self.requests[s] is None:
                assert row == [0] * self.pages_per_slot, (s, row)
                assert self.lengths[s] == 0, (s, self.lengths[s])
            else:
                owned.extend(row)
        free = list(self._free_pages)
        assert 0 not in owned and 0 not in free, "null page leaked"
        combined = owned + free
        assert len(combined) == len(set(combined)), "page owned twice"
        assert set(combined) == set(range(1, self.n_pages)), "page lost"
        assert sorted(occ + list(self._free_slots)) == list(range(self.n_slots))


class _DetokenizeWorker(threading.Thread):
    """Daemon thread draining emitted (request, token, time) triples.

    The decode loop's ``put`` never blocks (unbounded queue), so host-side
    detokenization can lag arbitrarily without stalling a decode step; the
    backlog drains fully at ``finish()``.
    """

    def __init__(self, detokenize):
        super().__init__(daemon=True)
        self._q: queue.Queue = queue.Queue()
        self._detok = detokenize
        self.results: dict[str, dict] = {}

    def put(self, rid: str, token: int, t: float) -> None:
        self._q.put((rid, token, t))

    def run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            rid, tok, t = item
            r = self.results.setdefault(rid, {"tokens": [], "text": [], "times": []})
            r["tokens"].append(tok)
            r["text"].append(self._detok(tok))
            r["times"].append(t)
            self._q.task_done()

    def finish(self) -> dict[str, dict]:
        self._q.put(None)
        self._q.join()
        self.join()
        return self.results


def _threefry_key(seed: int) -> np.ndarray:
    """Raw threefry key data for ``seed`` — the host-side equivalent of
    ``jax.random.PRNGKey`` (no device op, so admission never compiles)."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


class ServeEngine:
    """Continuous-batching serving engine (see module docstring)."""

    def __init__(
        self,
        cfg,
        params=None,
        *,
        max_concurrent_decodes: int = 4,
        max_prompt_len: int = 64,
        max_new_tokens: int = 32,
        page_size: int = 16,
        eos_id: int = -1,
        temperature: float = 0.0,
        seed: int = 0,
        detokenize=None,
        spec_decode: bool = False,
        draft_len: int = 4,
    ):
        assert page_size > 0 and page_size & (page_size - 1) == 0, page_size
        if spec_decode and draft_len < 1:
            raise ValueError(f"draft_len must be >= 1, got {draft_len}")
        self.cfg = cfg
        self.model = build_model(cfg)
        if not self.model.supports_paged_decode:
            raise ValueError(
                f"family {cfg.family!r} has no paged decode path; use "
                "BatchedServer for the recurrent families"
            )
        self.params = (
            params if params is not None else self.model.init(jax.random.PRNGKey(seed))
        )
        self.n_slots = max_concurrent_decodes
        self.page_size = page_size
        self.eos_id = eos_id
        self.temperature = temperature
        self.spec_decode = spec_decode
        self.draft_len = draft_len if spec_decode else 0
        self._detok = detokenize or (lambda t: f"<{t}>")

        bucket_cap = page_size
        while bucket_cap < max_prompt_len:
            bucket_cap *= 2
        self.buckets: list[int] = []
        b = page_size
        while b <= bucket_cap:
            self.buckets.append(b)
            b *= 2
        cap = bucket_cap + max_new_tokens
        self.pages_per_slot = -(-cap // page_size)
        self.capacity = self.pages_per_slot * page_size
        n_pool = self.n_slots * self.pages_per_slot + 1
        self.scheduler = SlotScheduler(self.n_slots, self.pages_per_slot, n_pool)
        self.cache = self.model.init_paged_cache(n_pool, page_size)

        self._compile_count = 0
        self._prefill_exe: dict = {}
        self._insert_exe: dict = {}
        self._decode_exe = None
        self._sample_exe: dict = {}
        self._verify_exe = None
        self._verify_sample_exe = None

    # ------------------------------------------------------------------
    # warmup: AOT-compile every executable the serve loop can need
    # ------------------------------------------------------------------
    @property
    def compile_count(self) -> int:
        """Number of XLA compiles this engine has performed (the jit-cache-
        miss counter of the no-recompile contract: stable across serve()
        once warmup() has run)."""
        return self._compile_count

    def _aot(self, fn, *avals, donate=()):
        exe = jax.jit(fn, donate_argnums=donate).lower(*avals).compile()
        self._compile_count += 1
        return exe

    def warmup(self) -> None:
        if self._decode_exe is not None:
            return
        model = self.model
        p_aval = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.params
        )
        c_aval = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.cache
        )
        len_aval = jax.ShapeDtypeStruct((), jnp.int32)
        for bkt in self.buckets:
            tok_aval = jax.ShapeDtypeStruct((1, bkt), jnp.int32)
            self._prefill_exe[bkt] = self._aot(
                model.prefill_paged, p_aval, tok_aval, len_aval
            )
            _, k_aval, v_aval = jax.eval_shape(
                model.prefill_paged, p_aval, tok_aval, len_aval
            )
            ids_aval = jax.ShapeDtypeStruct((bkt // self.page_size,), jnp.int32)
            self._insert_exe[bkt] = self._aot(
                model.insert_pages, c_aval, k_aval, v_aval, ids_aval, donate=(0,)
            )
        S, P = self.n_slots, self.pages_per_slot
        self._decode_exe = self._aot(
            model.decode_step_paged,
            p_aval,
            c_aval,
            jax.ShapeDtypeStruct((S, P), jnp.int32),
            jax.ShapeDtypeStruct((S,), jnp.int32),
            jax.ShapeDtypeStruct((S,), jnp.int32),
            donate=(1,),
        )
        V = self.cfg.vocab_size
        logits_dt = jax.eval_shape(
            model.prefill_paged,
            p_aval,
            jax.ShapeDtypeStruct((1, self.buckets[0]), jnp.int32),
            len_aval,
        )[0].dtype
        for n in (1, S):
            self._sample_exe[n] = self._aot(
                self._sample_fn,
                jax.ShapeDtypeStruct((n, V), logits_dt),
                jax.ShapeDtypeStruct((n, 2), jnp.uint32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
            )
        if self.spec_decode:
            Tv = self.draft_len + 1
            self._verify_exe = self._aot(
                model.verify_step_paged,
                p_aval,
                c_aval,
                jax.ShapeDtypeStruct((S, P), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S, Tv), jnp.int32),
                donate=(1,),
            )
            self._verify_sample_exe = self._aot(
                self._verify_sample_fn,
                jax.ShapeDtypeStruct((S, Tv, V), logits_dt),
                jax.ShapeDtypeStruct((S, 2), jnp.uint32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
            )

    def _sample_fn(self, logits, keys, steps):
        """Greedy argmax, or per-row categorical keyed by the request's
        fold-in stream — a row's sample never depends on the other slots."""
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def one(row, key, step):
            k = jax.random.fold_in(key, step)
            return jax.random.categorical(k, row / self.temperature)

        return jax.vmap(one)(logits, keys, steps).astype(jnp.int32)

    def _verify_sample_fn(self, logits, keys, steps):
        """Per-position sampling over a verify window ([S, T, V]): window
        position t of slot s uses ``fold_in(key_s, steps_s + t)`` — exactly
        the key the non-spec loop would consume for that emitted position,
        so the accepted stream replays the vanilla stream bit-for-bit."""
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def one(row, key, step):
            k = jax.random.fold_in(key, step)
            return jax.random.categorical(k, row / self.temperature)

        def per_slot(rows, key, base):
            offs = base + jnp.arange(rows.shape[0], dtype=jnp.int32)
            return jax.vmap(lambda r, s: one(r, key, s))(rows, offs)

        return jax.vmap(per_slot)(logits, keys, steps).astype(jnp.int32)

    # ------------------------------------------------------------------
    # serve loop
    # ------------------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for bkt in self.buckets:
            if n <= bkt:
                return bkt
        raise ValueError(
            f"prompt length {n} exceeds the largest bucket {self.buckets[-1]}"
        )

    def _admit(self, req: Request, worker, live: dict, fed: np.ndarray, clock):
        """Prefill + first sample for ``req``; returns the first-token
        timestamp.  A request whose ``max_new`` overruns the slot's page
        quota is truncated to the quota (flagged), never rejected: the
        emission budget ``capacity - n + 1`` is exact because the final
        emitted token needs no KV slot."""
        n = int(len(req.tokens))
        bkt = self._bucket_for(n)
        budget = min(req.max_new, self.capacity - n + 1)
        padded = np.zeros((1, bkt), np.int32)
        padded[0, :n] = np.asarray(req.tokens, np.int32)
        logits, k_new, v_new = self._prefill_exe[bkt](self.params, padded, np.int32(n))
        slot = self.scheduler.insert(req.id, n)
        page_ids = self.scheduler.block_tables[slot][: bkt // self.page_size]
        self.cache = self._insert_exe[bkt](
            self.cache, k_new, v_new, np.ascontiguousarray(page_ids)
        )
        lv = _Live(
            req=req,
            slot=slot,
            key=_threefry_key(req.seed),
            budget=budget,
            truncated=budget < req.max_new,
            history=[int(t) for t in req.tokens],
        )
        tok0 = int(
            self._sample_exe[1](logits, lv.key[None], np.zeros((1,), np.int32))[0]
        )
        lv.generated = 1
        lv.history.append(tok0)
        t_first = clock()
        worker.put(req.id, tok0, t_first)
        fed[slot] = tok0
        live[slot] = lv
        if (self.eos_id >= 0 and tok0 == self.eos_id) or lv.budget <= 1:
            self.scheduler.evict(slot)
            del live[slot]
            fed[slot] = 0
        return t_first, lv.truncated

    def serve(
        self, requests: list[Request], *, step_clock: bool = False
    ) -> tuple[dict, dict]:
        """Serve a workload to completion.  Requests are admitted once their
        ``arrival`` has passed (wall seconds, or decode-step index under
        ``step_clock``) and a slot is free, in arrival order.  Returns
        (per-request results, aggregate stats)."""
        self.warmup()
        sched = self.scheduler
        pending: deque[Request] = deque(sorted(requests, key=lambda r: r.arrival))
        worker = _DetokenizeWorker(self._detok)
        worker.start()
        live: dict[int, _Live] = {}
        fed = np.zeros((self.n_slots,), np.int32)
        keys = np.zeros((self.n_slots, 2), np.uint32)
        steps_arr = np.zeros((self.n_slots,), np.int32)
        ttft: dict[str, float] = {}
        queue_t: dict[str, float] = {}
        truncated: dict[str, bool] = {}
        t0 = time.perf_counter()
        step = 0
        emitted = 0
        spec_proposed = 0
        spec_accepted = 0
        decode_emitted = 0
        Tv = self.draft_len + 1

        def clock():
            return float(step) if step_clock else time.perf_counter() - t0

        while pending or live:
            now = clock()
            while pending and pending[0].arrival <= now and sched.has_free_slot():
                req = pending.popleft()
                # queue time ends at admission; ttft additionally pays the
                # prefill + first sample — they are separate stats
                queue_t[req.id] = clock() - req.arrival
                t_first, trunc = self._admit(req, worker, live, fed, clock)
                ttft[req.id] = t_first - req.arrival
                truncated[req.id] = trunc
                emitted += 1
            if not live:
                if step_clock:
                    step += 1
                else:
                    time.sleep(1e-4)
                continue
            for slot, lv in live.items():
                keys[slot] = lv.key
                steps_arr[slot] = lv.generated
            if self.spec_decode:
                window = np.zeros((self.n_slots, Tv), np.int32)
                drafts: dict[int, list] = {}
                for slot, lv in live.items():
                    d = prompt_lookup_draft(lv.history, self.draft_len)
                    drafts[slot] = d
                    window[slot, 0] = fed[slot]
                    if d:
                        window[slot, 1 : 1 + len(d)] = d
                logits, self.cache = self._verify_exe(
                    self.params,
                    self.cache,
                    np.ascontiguousarray(sched.block_tables),
                    np.ascontiguousarray(sched.lengths),
                    window,
                )
                toks = np.asarray(self._verify_sample_exe(logits, keys, steps_arr))
                step += 1
                t_now = clock()
                for slot in list(live):
                    lv = live[slot]
                    d = drafts[slot]
                    # accept the longest draft prefix the model re-derives;
                    # each acceptance frees one more verified position, and
                    # position a's sample is the bonus token — so a step
                    # emits a+1 tokens, capped by the emission budget
                    emit_room = lv.budget - lv.generated
                    a = 0
                    while a < min(len(d), emit_room - 1) and int(toks[slot, a]) == d[a]:
                        a += 1
                    emits = [int(toks[slot, j]) for j in range(a + 1)]
                    if self.eos_id >= 0 and self.eos_id in emits:
                        emits = emits[: emits.index(self.eos_id) + 1]
                    n_em = len(emits)
                    spec_proposed += len(d)
                    spec_accepted += min(a, n_em - 1)
                    for tok in emits:
                        worker.put(lv.req.id, tok, t_now)
                    emitted += n_em
                    decode_emitted += n_em
                    lv.history.extend(emits)
                    lv.generated += n_em
                    # rejected tail KV (positions past the last commit) is
                    # rolled back by this pointer alone — never copied out
                    sched.lengths[slot] += n_em
                    fed[slot] = emits[-1]
                    hit_eos = self.eos_id >= 0 and emits[-1] == self.eos_id
                    if hit_eos or lv.generated >= lv.budget:
                        sched.evict(slot)
                        del live[slot]
                        fed[slot] = 0
                continue
            logits, self.cache = self._decode_exe(
                self.params,
                self.cache,
                np.ascontiguousarray(sched.block_tables),
                np.ascontiguousarray(sched.lengths),
                fed,
            )
            toks = np.asarray(self._sample_exe[self.n_slots](logits, keys, steps_arr))
            step += 1
            t_now = clock()
            for slot in list(live):
                lv = live[slot]
                tok = int(toks[slot])
                lv.generated += 1
                sched.lengths[slot] += 1
                lv.history.append(tok)
                worker.put(lv.req.id, tok, t_now)
                emitted += 1
                decode_emitted += 1
                fed[slot] = tok
                hit_eos = self.eos_id >= 0 and tok == self.eos_id
                if hit_eos or lv.generated >= lv.budget:
                    sched.evict(slot)
                    del live[slot]
                    fed[slot] = 0
        wall = time.perf_counter() - t0
        raw = worker.finish()
        results = {
            rid: {
                "tokens": np.asarray(r["tokens"], np.int32),
                "text": "".join(r["text"]),
                "times": r["times"],
                "ttft_s": ttft[rid],
                "queue_time_s": queue_t[rid],
                "truncated": truncated[rid],
            }
            for rid, r in raw.items()
        }
        ttfts = sorted(ttft.values())
        queues = sorted(queue_t.values())

        def _pct(xs, q):
            return round(1e3 * float(np.percentile(xs, q)), 3) if xs else 0.0

        stats = {
            "requests": len(requests),
            "emitted_tokens": emitted,
            "live_tokens": int(sum(len(r["tokens"]) for r in results.values())),
            "decode_steps": step,
            "wall_s": round(wall, 4),
            "tok_per_s": round(emitted / max(wall, 1e-9), 1),
            "ttft_p50_ms": _pct(ttfts, 50),
            "ttft_p99_ms": _pct(ttfts, 99),
            "queue_p50_ms": _pct(queues, 50),
            "queue_p99_ms": _pct(queues, 99),
            "truncated_requests": int(sum(truncated.values())),
            "max_concurrent_decodes": self.n_slots,
            "page_size": self.page_size,
            "compile_count": self.compile_count,
            "spec_decode": self.spec_decode,
        }
        if self.spec_decode:
            stats["draft_len"] = self.draft_len
            stats["proposed_tokens"] = spec_proposed
            stats["accepted_tokens"] = spec_accepted
            stats["acceptance_rate"] = round(
                spec_accepted / max(spec_proposed, 1), 4
            )
            stats["tok_per_verify"] = round(decode_emitted / max(step, 1), 3)
        return results, stats


class BatchedServer:
    """Legacy static-batch driver: one prefill, lockstep decode, rows frozen
    at EOS.  Kept as the engine's oracle and for the recurrent families the
    paged engine doesn't cover."""

    def __init__(self, cfg, params=None, max_len: int = 512, seed: int = 0):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = (
            params if params is not None else self.model.init(jax.random.PRNGKey(seed))
        )
        self.max_len = max_len
        self._prefill = jax.jit(lambda p, b: self.model.prefill(p, b, max_len))
        self._decode = jax.jit(self.model.decode_step)

    def generate(
        self,
        prompts: np.ndarray,          # [B, S] int32 (right-aligned, padded)
        max_new_tokens: int = 32,
        eos_id: int = -1,
        temperature: float = 0.0,
        seed: int = 0,
    ) -> tuple[np.ndarray, dict]:
        B = prompts.shape[0]
        t0 = time.time()
        logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(prompts)})
        prefill_s = time.time() - t0

        key = jax.random.PRNGKey(seed)
        out = []
        done = np.zeros(B, bool)
        live = np.zeros(B, np.int64)
        # Finished rows are frozen: their emitted token is pinned to eos_id
        # (pad 0 when no EOS is configured) instead of whatever the model
        # keeps sampling past EOS, and that pinned token — not the raw
        # sample — is what feeds the next decode step, so a done row's cache
        # advances on a stable input while the rest of the batch drains.
        fill = eos_id if eos_id >= 0 else 0
        tok = self._sample(logits, temperature, key)
        jax.block_until_ready(tok)
        # time-to-first-token is its own stat (prefill + first sample), not
        # folded into the decode walltime
        ttft_s = time.time() - t0
        t1 = time.time()
        for i in range(max_new_tokens):
            emitted = np.where(done, fill, np.asarray(tok)).astype(np.int32)
            out.append(emitted)
            live += ~done          # the EOS token itself still counts live
            done |= emitted == eos_id
            if done.all() or i == max_new_tokens - 1:
                break
            logits, cache = self._decode(self.params, cache, jnp.asarray(emitted))
            key = jax.random.fold_in(key, i)
            tok = self._sample(logits, temperature, key)
        decode_s = time.time() - t1
        tokens = np.stack(out, axis=1)
        live_total = int(live.sum())
        stats = {
            "prefill_s": round(prefill_s, 4),
            "ttft_s": round(ttft_s, 4),
            "decode_s": round(decode_s, 4),
            "live_tokens": live_total,
            "decode_tok_per_s": round(live_total / max(decode_s, 1e-9), 1),
        }
        return tokens, stats

    @staticmethod
    def _sample(logits, temperature, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(
            jnp.int32
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-125m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--eos-id",
        type=int,
        default=-1,
        help="EOS token id; -1 disables early stop (rows always decode "
        "max-new tokens)",
    )
    ap.add_argument(
        "--engine",
        action="store_true",
        help="serve through the continuous-batching ServeEngine instead of "
        "the static-batch loop",
    )
    ap.add_argument("--max-concurrent", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument(
        "--spec-decode",
        action="store_true",
        help="speculative decoding (prompt-lookup draft + multi-token "
        "verify); requires --engine",
    )
    ap.add_argument(
        "--draft-len",
        type=int,
        default=4,
        help="max draft tokens proposed per verify step (with --spec-decode)",
    )
    args = ap.parse_args()
    use_compile_cache()
    if args.spec_decode and not args.engine:
        ap.error(
            "--spec-decode requires --engine: the static-batch "
            "BatchedServer has no draft/verify pipeline"
        )

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = np.random.default_rng(0)
    size = (args.batch, args.prompt_len)
    prompts = rng.integers(2, cfg.vocab_size, size=size).astype(np.int32)
    if args.engine:
        engine = ServeEngine(
            cfg,
            max_concurrent_decodes=args.max_concurrent,
            max_prompt_len=args.prompt_len,
            max_new_tokens=args.max_new,
            page_size=args.page_size,
            eos_id=args.eos_id,
            temperature=args.temperature,
            spec_decode=args.spec_decode,
            draft_len=args.draft_len,
        )
        reqs = [
            Request(id=f"r{i}", tokens=prompts[i], max_new=args.max_new)
            for i in range(args.batch)
        ]
        _, stats = engine.serve(reqs)
        print(json.dumps(stats, indent=1))
        return
    server = BatchedServer(cfg, max_len=args.prompt_len + args.max_new + 1)
    tokens, stats = server.generate(
        prompts,
        max_new_tokens=args.max_new,
        eos_id=args.eos_id,
        temperature=args.temperature,
    )
    print(json.dumps({"generated_shape": list(tokens.shape), **stats}, indent=1))


if __name__ == "__main__":
    main()
