"""Pallas TPU kernels: paged (block-table) KV-cache decode attention, single
query per slot (``paged_decode_attention``) and the multi-token speculative
verify generalization (``paged_verify_attention``).

The serving engine keeps every slot's KV cache as fixed-size pages in one
shared pool (``k_pages/v_pages [n_pages, page_size, KV, dh]``) addressed
through a per-slot block table (``[n_slots, pages_per_slot] int32`` of
physical page ids).  Insert/evict is then a page-table edit on the host —
no cache copy ever moves — and one decode step attends each slot's single
new query against only its own pages.

Grid = (n_slots, pages_per_slot) with the page index innermost
("arbitrary" ⇒ sequential on TPU): the block table and per-slot lengths ride
scalar prefetch (``PrefetchScalarGridSpec``) so the k/v BlockSpec index maps
chase ``block_table[slot, page]`` — the pool gather IS the DMA schedule, no
contiguous cache is ever materialized.  Online-softmax (m, l, acc) scratch
accumulates across a slot's pages exactly like the prefill flash kernel
accumulates across kv blocks; pages at or beyond ``lengths[slot]`` are
skipped whole via ``@pl.when`` and the partial tail page is masked by
position.  A slot with length 0 (free slot) contributes nothing and writes
a zero output tile.

Each k/v block is one whole page, every (local) kv head of it —
``(1, page_size, KV, dh)`` — and the kernel loops over kv heads: Mosaic
accepts a block's second-minor dim only whole or in multiples of 8, so a
one-head block over KV = 12 is refused.  The pool layout stays
``[n_pages, page_size, KV, dh]``, one contiguous DMA per page.

VMEM working set per slot is small — KV×G×dh query + page_size×KV×dh k/v +
KV×G×page_size f32 scores — decode is bandwidth-bound on the pool reads,
which is the point of paging: only live pages are ever streamed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(
    bt_ref,  # scalar prefetch: [S, P] int32 block table
    len_ref,  # scalar prefetch: [S] int32 valid kv length per slot
    q_ref,  # [1, KV, G, dh]
    k_ref,  # [1, page_size, KV, dh] — the page picked by the index map
    v_ref,
    o_ref,  # [1, KV, G, dh]
    m_scr,  # [KV, G, 1]
    l_scr,
    acc_scr,  # [KV, G, dh]
    *,
    page_size: int,
    n_pages: int,
    scale: float,
):
    s = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[s]
    base = ip * page_size

    @pl.when(base < length)
    def _body():
        qa = q_ref[0].astype(jnp.float32)  # [KV, G, dh]
        # widen the whole page first: Mosaic slices a head out of the
        # second-minor dim of an f32 value, not of a packed bf16 ref
        ka = k_ref[0].astype(jnp.float32)  # [page_size, KV, dh]
        va = v_ref[0].astype(jnp.float32)
        for h in range(qa.shape[0]):
            q, k, v = qa[h], ka[:, h, :], va[:, h, :]
            sc = (
                jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                * scale
            )  # [G, page_size]
            kpos = base + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(kpos < length, sc, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_scr[h] = m_new

    @pl.when(ip == n_pages - 1)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("head_scale", "interpret"))
def paged_decode_attention(
    q: jax.Array,  # [S, KV, G, dh] one query token per slot
    k_pages: jax.Array,  # [n_pages, page_size, KV, dh]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, pages_per_slot] int32 physical page ids
    lengths: jax.Array,  # [S] int32 valid kv positions (kpos < length attends)
    *,
    head_scale: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """Returns [S, KV, G, dh].  ``head_scale`` (0 ≡ dh**-0.5) pins the
    softmax scale to the unpadded head dim when dh carries lane padding.
    Block-table entries must be valid pool indices even for dead slots
    (the engine points them at the reserved null page)."""
    S, KV, G, dh = q.shape
    n_pool, page_size = k_pages.shape[0], k_pages.shape[1]
    P = block_tables.shape[1]
    scale = head_scale if head_scale else dh**-0.5

    kernel = functools.partial(
        _paged_decode_kernel,
        page_size=page_size,
        n_pages=P,
        scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, P),
        in_specs=[
            pl.BlockSpec((1, KV, G, dh), lambda s, ip, bt, lens: (s, 0, 0, 0)),
            pl.BlockSpec(
                (1, page_size, KV, dh),
                lambda s, ip, bt, lens: (bt[s, ip], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, page_size, KV, dh),
                lambda s, ip, bt, lens: (bt[s, ip], 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, KV, G, dh), lambda s, ip, bt, lens: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KV, G, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables, lengths, q, k_pages, v_pages)


def _paged_verify_kernel(
    bt_ref,  # scalar prefetch: [S, P] int32 block table
    len_ref,  # scalar prefetch: [S] int32 kv count valid for window position 0
    q_ref,  # [1, T, KV, G, dh] — the slot's whole draft window
    k_ref,  # [1, page_size, KV, dh] — the page picked by the index map
    v_ref,
    o_ref,  # [1, T, KV, G, dh]
    m_scr,  # [KV, T·G, 1]
    l_scr,
    acc_scr,  # [KV, T·G, dh]
    *,
    page_size: int,
    n_pages: int,
    n_draft: int,
    group: int,
    scale: float,
):
    """Speculative-verify attention: window position ``t`` of slot ``s``
    attends ``kpos < lengths[s] + t`` — the slot's paged history plus a
    causal intra-window mask over the draft tokens themselves (whose KV the
    engine has already written into the pages at positions
    ``lengths[s]-1 .. lengths[s]+T-2``).  Collapses the window into the
    sublane axis ([T·G, dh] queries per kv head) so the per-page
    online-softmax update is one dot + one masked exp, exactly the decode
    kernel's — at T=1 the arithmetic is instruction-for-instruction the
    decode kernel's, which the parity tests assert bitwise."""
    s = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[s]
    base = ip * page_size

    # A page contributes if any window row attends into it; the last row
    # (t = T-1) reaches kpos < length + T - 1.  length == 0 marks a dead
    # slot: skip every page so the zero-filled scratch writes exact zeros
    # (position 0 is unconditionally attended by every live row, so each
    # live row's running max is finite from the first page on).
    @pl.when((length > 0) & (base < length + n_draft - 1))
    def _body():
        qa = q_ref[0].astype(jnp.float32)  # [T, KV, G, dh]
        ka = k_ref[0].astype(jnp.float32)  # [page_size, KV, dh]
        va = v_ref[0].astype(jnp.float32)
        dh = qa.shape[-1]
        for h in range(qa.shape[1]):
            q = qa[:, h].reshape(n_draft * group, dh)
            k, v = ka[:, h, :], va[:, h, :]
            sc = (
                jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                * scale
            )  # [T*G, page_size]
            kpos = base + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            qt = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) // group
            sc = jnp.where(kpos < length + qt, sc, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_scr[h] = m_new

    @pl.when(ip == n_pages - 1)
    def _fin():
        kv, dh = o_ref.shape[2], o_ref.shape[-1]
        denom = jnp.maximum(l_scr[...], 1e-30)
        o = (acc_scr[...] / denom).astype(o_ref.dtype)  # [KV, T*G, dh]
        o_ref[0] = jnp.stack(
            [o[h].reshape(n_draft, group, dh) for h in range(kv)], axis=1
        )


@functools.partial(jax.jit, static_argnames=("head_scale", "interpret"))
def paged_verify_attention(
    q: jax.Array,  # [S, T, KV, G, dh] — T draft-window queries per slot
    k_pages: jax.Array,  # [n_pages, page_size, KV, dh]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, pages_per_slot] int32 physical page ids
    lengths: jax.Array,  # [S] int32 kv count valid for window position 0
    *,
    head_scale: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """Returns [S, T, KV, G, dh].  Same scalar-prefetch block-table grid as
    :func:`paged_decode_attention` — grid (S, P), page index innermost,
    the pool gather IS the DMA schedule — with the whole T-token draft
    window riding the query tile and a causal intra-window mask on top of
    the per-slot length mask.  ``lengths[s]`` counts the kv positions the
    FIRST window token attends (its own included), so T=1 is exactly the
    decode kernel.  Dead slots (length 0) write exact zeros."""
    S, T, KV, G, dh = q.shape
    page_size = k_pages.shape[1]
    P = block_tables.shape[1]
    scale = head_scale if head_scale else dh**-0.5

    kernel = functools.partial(
        _paged_verify_kernel,
        page_size=page_size,
        n_pages=P,
        n_draft=T,
        group=G,
        scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, P),
        in_specs=[
            pl.BlockSpec((1, T, KV, G, dh), lambda s, ip, bt, lens: (s, 0, 0, 0, 0)),
            pl.BlockSpec(
                (1, page_size, KV, dh),
                lambda s, ip, bt, lens: (bt[s, ip], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, page_size, KV, dh),
                lambda s, ip, bt, lens: (bt[s, ip], 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, T, KV, G, dh), lambda s, ip, bt, lens: (s, 0, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((KV, T * G, 1), jnp.float32),
            pltpu.VMEM((KV, T * G, 1), jnp.float32),
            pltpu.VMEM((KV, T * G, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, T, KV, G, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables, lengths, q, k_pages, v_pages)
