"""jit'd public wrappers around the Pallas kernels.

``interpret`` resolves automatically: on CPU (this container) kernels run in
interpret mode (the kernel body executed in Python — correctness path); on
TPU they compile to Mosaic.  Wrappers also handle rank padding (r → multiple
of 128 for MXU lane alignment, zero-padded so the math is unchanged),
batched leaves via vmap, and awkward (m, n).  The two TeZO pass kernels
(``tezo_perturb``, ``tezo_adam_update``, and LOZO through them) take their
block from ``tezo_tiles`` and cover a dim the block does not divide with a
partial last block, so a 50272-row vocabulary leaf is never copied.  The
other weight-leaf kernels zero-pad such a dim up to the tile multiple and
slice the tail off after the call — so prime-ish dims still get full-width
tiles instead of degrading to tiny divisors.

These wrappers are the *production* hot path for every ZO method: the
estimator routes all perturb/update leaf math through ``repro.core.dispatch``,
which calls into here whenever ``ZOConfig.kernel_mode`` resolves to "pallas"
(default on TPU; force with kernel_mode="pallas", which on CPU runs these
kernels in interpret mode — or pin it with ``set_interpret``).

  * TeZO family     → ``tezo_perturb`` / ``tezo_adam_update``
  * MeZO family + every method's dense-fallback 2-D leaves
                    → ``noise_perturb`` / ``noise_update_*`` (on-chip PRNG)
  * LOZO            → ``lozo_perturb`` (tezo tiling with τ ≡ 1)
  * SubZO           → ``subzo_perturb`` (tezo tiling with a Σ core)

Chained transitions (the 2q+1-pass schedule of core.zo_step): stacked-τ
``tezo_perturb`` / stacked-Σ ``subzo_perturb`` / ``lozo_chain`` apply two
deltas in one W round-trip (bridge and restore-into-update for the factor
methods), ``noise_perturb_pair`` is the dual-draw noise bridge, and every
update wrapper takes ``restore_probe``/``restore_scale`` (noise family) or
``tau_r``/``restore_scale`` (tezo_adam) to fold the last probe's restore
into the update pass.  All of them reproduce the replaced passes'
weight-dtype rounding — bitwise-identical trajectories, half the HBM
traffic on the merged passes.

Leaves too small/oddly shaped for tiles (biases, norm scales: ndim < 2 or a
dim < 8) always stay on the dense jnp path — see dispatch's eligibility
predicates.  ``input_output_aliases`` inside the kernels keeps the three
Algorithm-1 perturbation passes in-place in HBM (for the padded leaves of
the noise, SubZO and quant kernels the pad copy breaks aliasing).

Sharded dispatch hooks: the noise wrappers take ``offsets`` — the global
coordinates of this array's origin when it is one device's shard of a
mesh-partitioned leaf (core.dispatch derives them inside shard_map) — so
the counter streams stay functions of the *global* element; update wrappers
take ``decay`` (the decoupled weight-decay factor 1 − lr·wd) and fold it
into the kernels' scalar params instead of a separate full-W pass.

The FORWARD kernels are production code too (PR 4): ``flash_attention``
and ``selective_scan`` at the bottom are the hot-forward wrappers that
``core.dispatch.attention_fwd`` / ``selective_scan_fwd`` call, with the
same pad-and-mask tiling contract on awkward sequence/head dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import zo_noise
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.tezo_adam import tezo_adam_update as _adam
from repro.kernels.tezo_perturb import VMEM_BUDGET as TEZO_VMEM_BUDGET
from repro.kernels.tezo_perturb import tezo_perturb as _perturb
from repro.kernels.zo_noise import leaf_seed  # re-export for dispatch

_FORCE_INTERPRET: bool | None = None


def set_interpret(value: bool | None) -> None:
    """Override interpret-mode detection (tests force True)."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = value


def _interpret() -> bool:
    if _FORCE_INTERPRET is not None:
        return _FORCE_INTERPRET
    # Mosaic lowering exists only on TPU; every other backend (cpu, gpu)
    # gets the interpret path so kernel_mode="pallas" stays usable anywhere.
    return jax.default_backend() != "tpu"


def is_interpret() -> bool:
    """Will these kernels run in interpret mode (emulation, not Mosaic)?

    Public query for launchers/benchmarks that need to label or warn about
    interpret-mode results — True off-TPU or when forced via set_interpret.
    """
    return _interpret()


def interpret_forced() -> bool:
    """Was interpret mode explicitly pinned via ``set_interpret(True)``?

    The forward dispatch (core.dispatch.attention_fwd / selective_scan_fwd)
    uses this to distinguish a *test* override — run the real kernel via the
    interpreter, the cross-lowering parity path — from plain off-TPU
    auto-detection, where the production forward takes the XLA twin inside
    the kernel-modeled marker region instead (interpret-mode emulation in a
    model's hot forward would be pathologically slow and would wreck the
    dry-run's HLO costing).
    """
    return _FORCE_INTERPRET is True


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _map_leading(fn, *arrays):
    """vmap ``fn`` over the leading axis — by unrolled loop in interpret mode.

    The interpret-mode kernels pin their per-delta rounding with
    ``lax.cond`` fence branches (see kernels/fence.py).  Under vmap the
    fence predicate is batched, and jax lowers a batched cond to
    execute-both-branches + select — inlining the delta back into the
    surrounding program and losing exactly the codegen isolation the fence
    exists for.  Stacked leaves therefore unroll in interpret mode (small,
    CPU, tests) and keep the batched vmap lowering for Mosaic, where the
    kernel's VMEM store is a real boundary and vmap just maps the grid.
    """
    if not _interpret():
        return jax.vmap(fn)(*arrays)
    outs = [fn(*(a[i] for a in arrays)) for i in range(arrays[0].shape[0])]
    if isinstance(outs[0], tuple):
        return tuple(
            jnp.stack([o[j] for o in outs]) for j in range(len(outs[0]))
        )
    return jnp.stack(outs)


def _pad_rank(u, v, *taus, multiple: int = 128):
    r = u.shape[-1]
    r_pad = _round_up(r, multiple)
    if r_pad == r:
        return (u, v) + taus
    pad = [(0, 0)] * (u.ndim - 1) + [(0, r_pad - r)]
    return (
        jnp.pad(u, pad),
        jnp.pad(v, pad),
    ) + tuple(
        # τ may be [r] or a stacked [k, r] transition chain — pad the rank
        # (trailing) axis only
        jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, r_pad - t.shape[-1])])
        for t in taus
    )


def _pad_sigma(sigma, multiple: int = 128):
    """Zero-pad Σ's trailing [r, r] core (possibly stacked [k, r, r])."""
    r = sigma.shape[-1]
    r_pad = _round_up(r, multiple)
    if r_pad == r:
        return sigma
    pad = [(0, 0)] * (sigma.ndim - 2) + [(0, r_pad - r), (0, r_pad - r)]
    return jnp.pad(sigma, pad)


def _tile_padded(dim: int, pref: int, mult: int) -> tuple[int, int]:
    """(tile, padded_dim) for the pad-and-mask tiling of weight leaves.

    Picks the tile (a multiple of the hardware alignment ``mult``, between
    min(128, pref) and ``pref``) that minimizes the zero-padding — so clean
    dims stay exactly unpadded (preserving the kernels' in-place HBM
    aliasing) and awkward dims get full-width tiles with a masked tail
    (vocab 50257 → tile 128, 47 pad rows) instead of the old divisor
    search's degenerate tiny tiles.  The caller zero-pads the operands to
    ``padded_dim`` and slices the tail off the result; the kernels' math is
    unaffected (padded u/v rows are zero, padded noise is sliced away).
    """
    if dim <= pref:
        t = _round_up(dim, mult)
        return t, t
    best_t, best_pad = pref, _round_up(dim, pref) - dim
    for t in range(pref, min(128, pref) - 1, -mult):
        pad = _round_up(dim, t) - dim
        if pad == 0:
            return t, dim
        if pad < best_pad:
            best_t, best_pad = t, pad
    return best_t, dim + best_pad


# Hardware alignment for the two trailing tile dims: 16 sublanes covers both
# f32 (8) and bf16 (16); 128 is the lane width.
_SUBLANE, _LANE = 16, 128


def _pad_rows(a, rows: int):
    if a.shape[-2] == rows:
        return a
    pad = [(0, 0)] * (a.ndim - 2) + [(0, rows - a.shape[-2]), (0, 0)]
    return jnp.pad(a, pad)


def _weight_tiles(m: int, n: int, bm_pref: int = 256, bn_pref: int = 512):
    bm, m_pad = _tile_padded(m, bm_pref, _SUBLANE)
    bn, n_pad = _tile_padded(n, bn_pref, _LANE)
    return bm, bn, m_pad, n_pad


def _pad_w(w, m_pad: int, n_pad: int):
    m, n = w.shape
    if (m, n) == (m_pad, n_pad):
        return w
    return jnp.pad(w, [(0, m_pad - m), (0, n_pad - n)])


def _crop(out, m: int, n: int):
    if out.shape == (m, n):
        return out
    return out[:m, :n]


# ---------------------------------------------------------------------------
# TeZO family
# ---------------------------------------------------------------------------

# f32 [bm, bn] tiles each kernel body keeps in VMEM, as the v5e compiler
# allocates them: the f32 W and the reconstruction; the Adam body adds M, V.
_TEZO_F32_TILES = {"perturb": 2, "adam": 4}
# the block widths the rule tries (lane multiples)
_TEZO_BN = (256, 512, 1024)
# v5e HBM bytes per second, and the fixed cost of one grid step
_HBM_BPS, _STEP_S = 819e9, 0.35e-6


def _tezo_working_set(bm, bn, r_pad, k, kernel, w_bytes):
    """VMEM bytes of one grid step: the W block in and out and the u/v
    blocks, each double-buffered, the body's f32 tiles, and one scaled u
    block per delta (the Adam body adds u·diag(τ_M) and u²·diag(τ_V))."""
    factor_blocks = k + (2 if kernel == "adam" else 0)
    return (
        4 * bm * bn * w_bytes + 8 * (bm + bn) * r_pad
        + 4 * _TEZO_F32_TILES[kernel] * bm * bn + 4 * factor_blocks * bm * r_pad
    )


def _even_block(dim: int, cap: int, mult: int) -> int:
    """The ``mult``-aligned block that covers ``dim`` in as few steps as
    ``cap`` allows, evened out so the partial last block is as full as it
    can be (one block, rounded up, when the dim fits under ``cap``)."""
    return _round_up(-(-dim // -(-dim // cap)), mult)


@functools.lru_cache(maxsize=None)
def tezo_tiles(
    m: int, n: int, r_pad: int, k: int, kernel: str, w_bytes: int = 2
) -> tuple[int, int]:
    """(bm, bn) for one [m, n] leaf of a TeZO pass kernel.

    ``r_pad`` is the factors' rank as the kernel sees it, ``k`` the number
    of rank-r deltas chained before the final write (the perturb chain, or
    the restore rows the Adam pass folds in), ``kernel`` "perturb" or
    "adam".  For each block width it takes the tallest block whose working
    set fits ``TEZO_VMEM_BUDGET`` and keeps the one with the least modelled
    time: steps × (step cost + W in and out + the v block) + the u blocks.
    The grid runs j innermost, so u is fetched once per row of blocks and
    v on every step: factor bytes are r_pad/bm of the W bytes moved, and a
    tall block keeps them small.  A dim the block does not divide gets a
    partial last block in the kernel, not a padded copy.
    """
    best = None
    for cap in _TEZO_BN:
        bn = _even_block(n, cap, _LANE)
        fixed = _tezo_working_set(0, bn, r_pad, k, kernel, w_bytes)
        per_row = _tezo_working_set(1, bn, r_pad, k, kernel, w_bytes) - fixed
        bm_cap = (TEZO_VMEM_BUDGET - fixed) // per_row // _SUBLANE * _SUBLANE
        if bm_cap < _SUBLANE:
            continue
        bm = _even_block(m, bm_cap, _SUBLANE)
        rows, cols = -(-m // bm), -(-n // bn)
        step_bytes = 2 * bm * bn * w_bytes + 4 * bn * r_pad
        t = (rows * cols * (_STEP_S + step_bytes / _HBM_BPS)
             + rows * 4 * bm * r_pad / _HBM_BPS)
        if best is None or t < best[0]:
            best = (t, bm, bn)
    assert best is not None, (m, n, r_pad, k, kernel)
    return best[1], best[2]


def _decay_scalar(decay):
    """Normalize the optional weight-decay factor to a kernel scalar."""
    return 1.0 if decay is None else decay


def tezo_perturb(w, u, v, tau, scale, *, decay=None, pad_rank: bool = True):
    """decay·W + scale·(u·diag(τ))·vᵀ for 2-D or leading-batched W.

    ``decay`` is the decoupled weight-decay factor 1 − lr·wd, fused into the
    same HBM pass on update touches; None (≡ 1.0) on perturbation touches.

    Transition chains: a stacked ``tau`` [..., k, r] with per-delta ``scale``
    [k] applies k rank-r deltas in ONE W round-trip (the chained bridge /
    restore-into-update of core.zo_step), each delta rounding to the weight
    dtype exactly as its own pass would — bitwise identical to k separate
    calls.  ``decay`` applies to the last delta only.
    """
    if w.ndim > 2:
        fn = functools.partial(
            tezo_perturb, scale=scale, decay=decay, pad_rank=pad_rank
        )
        return _map_leading(fn, w, u, v, tau)
    if pad_rank and not _interpret():
        u, v, tau = _pad_rank(u, v, tau)
    m, n = w.shape
    r = u.shape[-1]
    k = tau.reshape((-1, r)).shape[0]
    bm, bn = tezo_tiles(m, n, r, k, "perturb", w.dtype.itemsize)
    return _perturb(
        w, u, v, tau, scale, _decay_scalar(decay), bm=bm, bn=bn,
        interpret=_interpret(),
    )


def tezo_adam_update(
    w, u, v, tau_m, tau_v, lr, eps=1e-5, *, decay=None,
    tau_r=None, restore_scale=0.0, pad_rank: bool = True,
):
    """Fused TeZO-Adam update; ``tau_r`` + ``restore_scale`` fold the last
    probe's +ρ·recon(τ_r) restore into the same pass (restore-into-update —
    see kernels/tezo_adam.py; bitwise identical to the separate restore)."""
    if w.ndim > 2:
        fn = functools.partial(
            tezo_adam_update, lr=lr, eps=eps, decay=decay,
            restore_scale=restore_scale, pad_rank=pad_rank,
        )
        if tau_r is None:
            return _map_leading(fn, w, u, v, tau_m, tau_v)
        return _map_leading(
            lambda wi, ui, vi, tmi, tvi, tri: fn(wi, ui, vi, tmi, tvi, tau_r=tri),
            w, u, v, tau_m, tau_v, tau_r,
        )
    if pad_rank and not _interpret():
        if tau_r is None:
            u, v, tau_m, tau_v = _pad_rank(u, v, tau_m, tau_v)
        else:
            u, v, tau_m, tau_v, tau_r = _pad_rank(u, v, tau_m, tau_v, tau_r)
    m, n = w.shape
    r = u.shape[-1]
    k = 0 if tau_r is None else tau_r.reshape((-1, r)).shape[0]
    bm, bn = tezo_tiles(m, n, r, k, "adam", w.dtype.itemsize)
    return _adam(
        w, u, v, tau_m, tau_v, lr, eps, _decay_scalar(decay), tau_r,
        restore_scale, bm=bm, bn=bn, interpret=_interpret(),
    )


# ---------------------------------------------------------------------------
# Dense on-chip-noise family (MeZO + dense-fallback leaves)
# ---------------------------------------------------------------------------


def _batch_seeds(seed, batch: int, offset=None):
    """Distinct Threefry key per leading-batch slice.

    Derived by encrypting the *global* slice index under the parent key —
    NOT by XOR-ing it in, which is commutative: nested leading dims (e.g. a
    [L, E, m, n] expert stack) peel one dim per recursion, and k1^i^j would
    collide for slices (i, j) and (j, i).  Re-keying through the cipher
    makes each nesting level's derivation injective and order-sensitive.
    ``offset`` is the global index of local slice 0 when the leading dim is
    sharded over the mesh (see core.dispatch) — None/0 when unsharded.
    """
    idx = jnp.arange(batch, dtype=jnp.uint32)
    if offset is not None:
        idx = idx + jnp.asarray(offset, jnp.int32).astype(jnp.uint32)
    s0, s1 = zo_noise.threefry2x32(
        seed[0], seed[1], idx, jnp.uint32(0x5EED51CE)
    )
    return jnp.stack([s0, s1], axis=-1)


def _split_offsets(offsets):
    """(leading-dim offset, remaining offsets) for one vmap recursion level."""
    if offsets is None:
        return None, None
    return offsets[0], offsets[1:]


def _noise_base(offsets):
    """int32[2] global (row0, col0) for the 2-D base case, or None."""
    if offsets is None:
        return None
    return offsets[-2:].astype(jnp.int32)


def noise_perturb(w, seed, scale, *, probe: int = 0, offsets=None):
    """W + scale·z with z ~ N(0, I) generated on-chip (counter PRNG).

    ``seed`` is the uint32[2] leaf key from ``leaf_seed(key_t, path)``; the
    draw is a pure function of (seed, probe, *global* element coords) so the
    three Algorithm-1 passes replay it exactly.  ``offsets`` (int32[w.ndim])
    holds the global coordinates of this array's origin when ``w`` is one
    device's shard of a mesh-partitioned leaf — the stream is then identical
    to the unsharded one, element for element.
    """
    if w.ndim > 2:
        lead = w.shape[0]
        off0, rest = _split_offsets(offsets)
        fn = functools.partial(noise_perturb, scale=scale, probe=probe, offsets=rest)
        return _map_leading(fn, w, _batch_seeds(seed, lead, off0))
    m, n = w.shape
    assert m < zo_noise.MAX_ROWS, (m, "row index must fit 24 bits")
    probes = probe if isinstance(probe, tuple) else (probe,)
    for p in probes:
        assert 0 <= p < zo_noise.MAX_PROBES, (p, "probe id must fit 8 bits")
    bm, bn, m_pad, n_pad = _weight_tiles(m, n)
    out = zo_noise.noise_perturb(
        _pad_w(w, m_pad, n_pad), seed, scale, base=_noise_base(offsets),
        probe=probe, bm=bm, bn=bn, interpret=_interpret(),
    )
    return _crop(out, m, n)


def noise_perturb_pair(
    w, seed, scale_a, scale_b, *, probe_a: int, probe_b: int, offsets=None
):
    """Chained bridge: W + scale_a·z_a + scale_b·z_b in ONE W round-trip.

    The dual-draw kernel generates both probes' z from the counter PRNG in
    the same tile visit, rounding to the weight dtype between the deltas —
    bitwise identical to two ``noise_perturb`` passes (same per-probe
    streams), at half the HBM traffic.
    """
    scales = jnp.stack([
        jnp.asarray(scale_a, jnp.float32), jnp.asarray(scale_b, jnp.float32)
    ])
    return noise_perturb(
        w, seed, scales, probe=(probe_a, probe_b), offsets=offsets
    )


def _noise_update(
    w, seed, kappas, hyp, m_buf=None, v_buf=None, *, variant,
    restore_probe=None, offsets=None,
):
    if w.ndim > 2:
        lead = w.shape[0]
        off0, rest = _split_offsets(offsets)
        seeds = _batch_seeds(seed, lead, off0)
        kw = dict(variant=variant, restore_probe=restore_probe, offsets=rest)
        if variant == "sgd":
            return _map_leading(
                lambda wi, si: _noise_update(wi, si, kappas, hyp, **kw),
                w, seeds,
            )
        if variant == "momentum":
            return _map_leading(
                lambda wi, si, mi: _noise_update(wi, si, kappas, hyp, mi, **kw),
                w, seeds, m_buf,
            )
        return _map_leading(
            lambda wi, si, mi, vi: _noise_update(
                wi, si, kappas, hyp, mi, vi, **kw
            ),
            w, seeds, m_buf, v_buf,
        )
    m, n = w.shape
    assert m < zo_noise.MAX_ROWS, (m, "row index must fit 24 bits")
    assert kappas.shape[0] < zo_noise.MAX_PROBES
    bm, bn, m_pad, n_pad = _weight_tiles(m, n)
    pad = functools.partial(_pad_w, m_pad=m_pad, n_pad=n_pad)
    out = zo_noise.noise_update(
        pad(w), seed, kappas, hyp,
        None if m_buf is None else pad(m_buf),
        None if v_buf is None else pad(v_buf),
        base=_noise_base(offsets),
        variant=variant, restore_probe=restore_probe,
        bm=bm, bn=bn, interpret=_interpret(),
    )
    return tuple(_crop(o, m, n) for o in out)


def _noise_hyp(lr, beta1=0.0, beta2=0.0, eps=0.0, decay=None, restore_scale=0.0):
    """[lr, β₁, β₂, ε, decay, restore…] f32 scalars for the fused update
    kernels (restore = the scale(s) of a chained restore-into-update — a
    single +ρ for the sequential chain, the [3q]-delta trajectory restore
    for a probe-parallel step)."""
    rs = jnp.asarray(
        restore_scale if not isinstance(restore_scale, (list, tuple))
        else jnp.stack([jnp.asarray(s, jnp.float32) for s in restore_scale]),
        jnp.float32,
    ).reshape(-1)
    return jnp.concatenate([
        jnp.stack([
            jnp.asarray(lr, jnp.float32), jnp.asarray(beta1, jnp.float32),
            jnp.asarray(beta2, jnp.float32), jnp.asarray(eps, jnp.float32),
            jnp.asarray(_decay_scalar(decay), jnp.float32),
        ]),
        rs,
    ])


def noise_update_sgd(
    w, seed, kappas, lr, *, decay=None,
    restore_probe=None, restore_scale=0.0, offsets=None,
):
    """W ← decay·W − lr·(mean_i κ_i z_i): probe mean, decoupled weight decay
    and update fused in one pass; ``restore_probe`` folds the chained
    +restore_scale·z restore into the same pass."""
    hyp = _noise_hyp(lr, decay=decay, restore_scale=restore_scale)
    return _noise_update(
        w, seed, kappas, hyp, variant="sgd",
        restore_probe=restore_probe, offsets=offsets,
    )[0]


def noise_update_momentum(
    w, m_buf, seed, kappas, lr, beta1, *, decay=None,
    restore_probe=None, restore_scale=0.0, offsets=None,
):
    """Fused M ← β₁M + (1−β₁)g; W ← decay·W − lr·M.  Returns (w', m')."""
    hyp = _noise_hyp(lr, beta1, decay=decay, restore_scale=restore_scale)
    return _noise_update(
        w, seed, kappas, hyp, m_buf, variant="momentum",
        restore_probe=restore_probe, offsets=offsets,
    )


def noise_update_adam(
    w, m_buf, v_buf, seed, kappas, lr, beta1, beta2, eps, *,
    decay=None, restore_probe=None, restore_scale=0.0, offsets=None,
):
    """Fused dense-Adam: both moment buffers ride the W grid (one HBM
    round-trip each instead of materializing g).  Returns (w', m', v')."""
    hyp = _noise_hyp(lr, beta1, beta2, eps, decay, restore_scale)
    return _noise_update(
        w, seed, kappas, hyp, m_buf, v_buf, variant="adam",
        restore_probe=restore_probe, offsets=offsets,
    )


# ---------------------------------------------------------------------------
# LOZO / SubZO
# ---------------------------------------------------------------------------


def lozo_perturb(w, u, v, scale, *, decay=None):
    """decay·W + scale·(U·Vᵀ): LOZO's Z is the TeZO tiling with τ ≡ 1."""
    tau = jnp.ones(u.shape[:-2] + (u.shape[-1],), jnp.float32)
    return tezo_perturb(w, u, v, tau, scale, decay=decay)


def lozo_chain(w, u, v_a, v_b, scale_a, scale_b, *, decay=None):
    """Two LOZO deltas — scale_a·U·V_aᵀ then scale_b·U·V_bᵀ — in ONE W pass.

    The chained bridge (restore V_i + perturb V_{i+1}) and restore-into-
    update (restore V_q + apply −lr·U·kvᵀ) both share the window-lazy U, so
    the pass is the TeZO chain kernel with STACKED fresh factors: u/v widen
    to 2r and two 0/1 τ rows select each half.  The masked-out half of each
    dot contributes exact zeros, so the result is bitwise identical to two
    separate ``lozo_perturb`` passes; ``decay`` applies to the second delta
    only (the update touch).
    """
    return lozo_chain_k(w, u, (v_a, v_b), (scale_a, scale_b), decay=decay)


def lozo_chain_k(w, u, vs, scales, *, decay=None):
    """k LOZO deltas — scaleᵢ·U·Vᵢᵀ in chain order — in ONE W round-trip.

    The k-ary generalization of ``lozo_chain`` (the probe-parallel step's
    catch-up chains and trajectory restores need arbitrary k): u/v widen to
    k·r and the τ rows are eye(k) repeated over the rank axis, so row i
    selects exactly the i-th V block — each delta bitwise identical to its
    own ``lozo_perturb`` pass; ``decay`` applies to the last delta only.
    """
    k = len(vs)
    r = u.shape[-1]
    batch = u.shape[:-2]
    uk = jnp.concatenate([u] * k, axis=-1) if k > 1 else u
    vk = jnp.concatenate(list(vs), axis=-1) if k > 1 else vs[0]
    taus = jnp.repeat(jnp.eye(k, dtype=jnp.float32), r, axis=1)   # [k, k·r]
    taus = jnp.broadcast_to(taus, batch + (k, k * r))
    scale_arr = jnp.stack([jnp.asarray(s, jnp.float32) for s in scales])
    return tezo_perturb(w, uk, vk, taus, scale_arr, decay=decay)


def subzo_perturb(w, u, v, sigma, scale, *, decay=None, pad_rank: bool = True):
    """decay·W + scale·(U·Σ·Vᵀ) for 2-D or leading-batched W.

    A stacked ``sigma`` [..., k, r, r] with ``scale`` [k] applies the
    perturbation chain's merged transitions in one pass (see
    zo_noise.subzo_perturb); decay hits the last delta only.
    """
    if w.ndim > 2:
        fn = functools.partial(
            subzo_perturb, scale=scale, decay=decay, pad_rank=pad_rank
        )
        return _map_leading(fn, w, u, v, sigma)
    if pad_rank and not _interpret():
        u, v = _pad_rank(u, v)[:2]
        sigma = _pad_sigma(sigma)
    m, n = w.shape
    bm, bn, m_pad, n_pad = _weight_tiles(m, n)
    out = zo_noise.subzo_perturb(
        _pad_w(w, m_pad, n_pad), _pad_rows(u, m_pad), _pad_rows(v, n_pad),
        sigma, scale, _decay_scalar(decay), bm=bm, bn=bn, interpret=_interpret(),
    )
    return _crop(out, m, n)


# ---------------------------------------------------------------------------
# Attention / SSM — the forward-path kernels, same pad-and-mask contract as
# the ZO weight-leaf kernels: awkward sequence/head dims are zero-padded up
# to the tile multiple (via _tile_padded) instead of degrading the tile size
# through divisor search, and the tail is masked/sliced after the call.
# ---------------------------------------------------------------------------


def _pad_axis(a, axis: int, target: int):
    if a.shape[axis] == target:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, target - a.shape[axis])
    return jnp.pad(a, pad)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0, bq=128, bk=128):
    """Fused flash attention with pad-and-mask tiling.

    Awkward S/T pad to the sublane-aligned tile (padded kv columns masked
    in-kernel via ``kv_len``, padded q rows sliced off); an awkward head dim
    pads to the lane multiple with the softmax scale pinned to the true dh
    (zero-padded q/k columns contribute nothing to the scores and padded v
    columns produce sliced-off output columns).
    """
    B, S, H, dh = q.shape
    T = k.shape[1]
    bq_t, s_pad = _tile_padded(S, bq, _SUBLANE)
    bk_t, t_pad = _tile_padded(T, bk, _SUBLANE)
    # sublane-align a truly awkward head dim; aligned dims (the ubiquitous
    # 64/128) pass through untouched — Mosaic pads sub-lane minor dims in
    # VMEM implicitly, so padding dh=64 to the 128 lane width here would
    # double the q/k/v/o HBM traffic for nothing
    dh_pad = _round_up(dh, _SUBLANE)
    out = _flash(
        _pad_axis(_pad_axis(q, 1, s_pad), 3, dh_pad),
        _pad_axis(_pad_axis(k, 1, t_pad), 3, dh_pad),
        _pad_axis(_pad_axis(v, 1, t_pad), 3, dh_pad),
        causal=causal, window=window, q_offset=int(q_offset),
        bq=bq_t, bk=bk_t, kv_len=T, head_scale=dh ** -0.5,
        interpret=_interpret(),
    )
    if (s_pad, dh_pad) != (S, dh):
        out = out[:, :S, :, :dh]
    return out


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths):
    """Paged (block-table) KV decode attention with pad-and-mask tiling.

    ``q [S, H, dh]`` (one token per slot), ``k_pages/v_pages
    [n_pages, page_size, KV, dh]``, ``block_tables [S, P] int32``,
    ``lengths [S] int32``; returns ``[S, H, dh]``.  An awkward head dim
    pads to the sublane multiple with the softmax scale pinned to the true
    dh; an awkward GQA group width pads to the sublane multiple too (the
    zero query rows produce sliced-off output rows).  ``page_size`` is an
    engine knob and is expected to be sublane-aligned already (the default
    serving page is 16).
    """
    from repro.kernels.decode_attention import paged_decode_attention as _paged

    S, H, dh = q.shape
    KV = k_pages.shape[2]
    G = H // KV
    g_pad = _round_up(G, 8)
    dh_pad = _round_up(dh, _SUBLANE)
    qg = q.reshape(S, KV, G, dh)
    qg = _pad_axis(_pad_axis(qg, 2, g_pad), 3, dh_pad)
    out = _paged(
        qg,
        _pad_axis(k_pages, 3, dh_pad),
        _pad_axis(v_pages, 3, dh_pad),
        block_tables,
        lengths,
        head_scale=dh**-0.5,
        interpret=_interpret(),
    )
    out = out[:, :, :G, :dh]
    return out.reshape(S, H, dh)


def paged_verify_attention(q, k_pages, v_pages, block_tables, lengths):
    """Speculative-verify paged attention with pad-and-mask tiling.

    ``q [S, T, H, dh]`` (the T-token draft window per slot), pages/tables/
    lengths as in :func:`paged_decode_attention` — ``lengths[s]`` is the kv
    count the first window position attends, window position t attends
    ``kpos < lengths[s] + t``.  Returns ``[S, T, H, dh]``.  Same padding
    contract as the decode wrapper: GQA group and head dim pad to the
    sublane multiple (zero query rows slice off, softmax scale pinned to
    the true dh); at T=1 this is exactly the decode wrapper's call shape.
    """
    from repro.kernels.decode_attention import paged_verify_attention as _verify

    S, T, H, dh = q.shape
    KV = k_pages.shape[2]
    G = H // KV
    g_pad = _round_up(G, 8)
    dh_pad = _round_up(dh, _SUBLANE)
    qg = q.reshape(S, T, KV, G, dh)
    qg = _pad_axis(_pad_axis(qg, 3, g_pad), 4, dh_pad)
    out = _verify(
        qg,
        _pad_axis(k_pages, 3, dh_pad),
        _pad_axis(v_pages, 3, dh_pad),
        block_tables,
        lengths,
        head_scale=dh**-0.5,
        interpret=_interpret(),
    )
    out = out[:, :, :, :G, :dh]
    return out.reshape(S, T, H, dh)


def quant_matmul(x, codes, lut, xu, qv, *, bits: int):
    """x @ (dequant(codes) + qu·diag(acc)·qvᵀ) with in-tile LUT dequant.

    ``x [M, K]``, ``codes [Kw, N]`` uint32 plane-packed (see
    core.quant.pack_codes), ``lut [N, 2**bits]`` f32 *scaled* per-channel
    table, ``xu [M, r]`` the precomputed ``x @ (qu·acc)`` factor half,
    ``qv [N, r]``.  Pad-and-mask tiling as everywhere else: M/N pad to the
    weight tiles, K pads to the packed row count (those x columns are zero,
    so the pack-pad code rows are inert), the LUT lane-pads to 128, and the
    rank lane-pads off-interpret.  Returns ``[M, N]`` in x's dtype.
    """
    from repro.kernels.quant_matmul import quant_matmul as _qmm

    m, k = x.shape
    kw, n = codes.shape
    kp = kw * (32 // bits)
    r = qv.shape[-1]
    bm, bn, m_pad, n_pad = _weight_tiles(m, n)
    rp = r if _interpret() else _round_up(r, _LANE)
    out = _qmm(
        _pad_axis(_pad_axis(x, 0, m_pad), 1, kp),
        _pad_axis(codes, 1, n_pad),
        _pad_axis(_pad_axis(lut, 0, n_pad), 1, _LANE),
        _pad_axis(_pad_axis(xu, 0, m_pad), 1, rp),
        _pad_axis(_pad_axis(qv, 0, n_pad), 1, rp),
        bits=bits, bm=bm, bn=bn, interpret=_interpret(),
    )
    return _crop(out, m, n)


def selective_scan(x, dt, a, b, c, h0, *, bd=128, bs=2048):
    """Mamba-1 selective scan; VMEM-resident state on TPU (see
    kernels/selective_scan.py), interpret-mode oracle path on CPU.

    Pad-and-mask tiling: an awkward channel dim D pads to the tile multiple
    (zero channels evolve zero state, sliced off) and an awkward sequence
    pads with identity timesteps — dt ≡ 0 ⇒ exp(0·A) = 1 and a zero input
    injection, so h_last is exact and the padded y tail is sliced off.
    """
    from repro.kernels.selective_scan import selective_scan as _scan

    B, S, D = x.shape
    bd_t, d_pad = _tile_padded(D, bd, _SUBLANE)
    bs_t, s_pad = _tile_padded(S, bs, _SUBLANE)
    if (d_pad, s_pad) != (D, S):
        x = _pad_axis(_pad_axis(x, 1, s_pad), 2, d_pad)
        dt = _pad_axis(_pad_axis(dt, 1, s_pad), 2, d_pad)
        a = _pad_axis(a, 0, d_pad)
        b = _pad_axis(b, 1, s_pad)
        c = _pad_axis(c, 1, s_pad)
        h0 = _pad_axis(h0, 1, d_pad)
    y, h_last = _scan(x, dt, a, b, c, h0, bd=bd_t, bs=bs_t, interpret=_interpret())
    if (d_pad, s_pad) != (D, S):
        y = y[:, :S, :D]
        h_last = h_last[:, :D]
    return y, h_last
