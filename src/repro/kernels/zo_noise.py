"""Pallas TPU kernels: fused dense-noise ZO perturb/update with on-chip PRNG.

The MeZO baselines (and every method's dense-fallback leaves) perturb with a
parameter-sized Gaussian ``z`` — the naive lowering materializes it in HBM on
each of the four leaf touches per step (three Algorithm-1 passes + update),
which is exactly the traffic the fused TeZO kernels eliminate for the
low-rank family.  These kernels give the dense methods the same one-HBM-
round-trip treatment: ``z`` is generated *on-chip per tile* and never leaves
VMEM.

The generator is counter-based (stateless): each element's normal draw is a
pure function of ``(key_t, path-hash, probe, row, col)`` via Threefry-2x32
(20 rounds, the Random123/JAX block cipher) + Box–Muller.  That is what makes
the whole scheme work:

  * the three Algorithm-1 passes (+ρ, −2ρ, +ρ) and the update regenerate
    bit-identical ``z`` from the same counters — nothing is stored;
  * the stream is independent of grid/tile order, so any tiling (including
    the pad-and-mask tail handling in ``ops.py``) sees the same noise;
  * ``ref.counter_normal_ref`` replays the generator in pure jnp, locking the
    kernel math bitwise in interpret mode.

We deliberately implement the counter cipher with in-kernel vector ops
(add/xor/rotate on uint32) rather than ``pltpu.prng_random_bits``: the
hardware PRNG's stream is opaque (no oracle could replay it), is stateful
per-core (tile-order dependent), and has no CPU interpret-mode lowering on
this JAX version — while Threefry is ~40 VPU ops per 2 words, negligible
against the HBM traffic these kernels exist to remove.

Counter layout: key = (key_t[0] ^ path_hash, key_t[1]), counter =
(col, row | probe << 24).  Rows are bounded by 2^24 and probes by 2^8 —
checked in ``ops.py`` — so (leaf, probe, element) → counter is injective.

Sharded dispatch: the (row, col) fed to the cipher are *global* element
coordinates.  Under ``shard_map`` each device runs these kernels on its local
shard and passes ``base`` — the global coordinates of the shard's (0, 0)
element, derived from the leaf's PartitionSpec + the device's mesh position
(see ``core.dispatch``) — so the stream is a pure function of the global
element, bit-identical across mesh layouts (1×1, 8×1, 2×4, TP-split, …).

NOTE the on-chip stream is *different* from ``jax.random.normal`` — MeZO
pallas-vs-xla parity is therefore statistical (moments/covariance, see
tests/test_zo_noise.py) plus exact three-pass self-consistency, not bitwise.

The update kernels fuse the q-SPSA probe mean ``g = mean_i κ_i z_i`` (probes
looped in-kernel over the resident tile) and the optimizer rule:

  sgd        W ← W − lr·g
  momentum   M ← β₁M + (1−β₁)g ;            W ← W − lr·M
  adam       ... V ← β₂V + (1−β₂)g² ;       W ← W − lr·M/√(V+ε)

so MeZO-m/MeZO-Adam's dense moment buffers also make exactly one HBM
round-trip, and ``q_probes > 1`` stops looping dense buffers in Python.

Chained transitions (core.zo_step's perturbation-chain schedule):

  * ``noise_perturb`` takes a *tuple* of static probe ids with per-probe
    scales — the dual-draw bridge that applies the restore of probe i and
    the perturb of probe i+1 in one W round-trip, generating BOTH z's from
    the counter PRNG in the same tile visit (the PRNG is ~40 VPU ops per 2
    words; the pass is HBM-bound, so the second draw is free);
  * the update kernels take ``restore_probe`` (static) + a restore scale in
    ``hyp[5]`` and add back the last probe's +ρ·z before the optimizer
    math, in the same pass.

Each fused-in delta casts to the weight dtype and back to f32 exactly where
the replaced HBM round-trip would have, so the chained trajectory is BITWISE
identical to the unchained one within the pallas mode: chained and unchained
draw identical per-probe counter streams — the same (key, probe, global
coords) → the same z, not merely the same distribution.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fence
from repro.utils.tree import _path_hash

# Threefry-2x32 rotation schedule (Random123), alternated every 4 rounds.
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA
MAX_ROWS = 1 << 24   # row index shares a counter word with the probe id
MAX_PROBES = 1 << 8


def _rotl(x: jax.Array, d: int) -> jax.Array:
    return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))


def threefry2x32(k0, k1, c0, c1):
    """Standard 20-round Threefry-2x32 block cipher (Random123 §3).

    All args uint32 (scalars or broadcastable arrays); returns two uint32
    words.  Matches the published Random123 test vectors — locked by
    tests/test_zo_noise.py — so the stream is a spec, not an implementation
    accident.
    """
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    x0 = c0 + ks[0]
    x1 = c1 + ks[1]
    for rnd in range(5):
        for d in _ROTATIONS[rnd % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, d) ^ x0
        x0 = x0 + ks[(rnd + 1) % 3]
        x1 = x1 + ks[(rnd + 2) % 3] + jnp.uint32(rnd + 1)
    return x0, x1


def _u24_to_f32(x: jax.Array) -> jax.Array:
    return x.astype(jnp.int32).astype(jnp.float32)


def counter_normal(k0, k1, rows, cols, probe: int) -> jax.Array:
    """N(0,1) f32 draw per (row, col) element via Threefry + Box–Muller.

    ``rows``/``cols`` are uint32 arrays of the output shape holding *global*
    element coordinates — the draw depends only on them (plus key/probe),
    never on tiling, so per-tile generation inside the kernels and the
    whole-array oracle agree bitwise.
    """
    c1 = rows | (jnp.uint32(probe) << jnp.uint32(24))
    b0, b1 = threefry2x32(k0, k1, cols, c1)
    # 24-bit mantissa uniforms in (0, 1): u ∈ [2^-25, 1 - 2^-25].  The
    # shifted words are < 2^24, so going through int32 is exact — and it is
    # the cast Mosaic lowers (it has no uint32 -> float32 conversion).
    u1 = _u24_to_f32(b0 >> jnp.uint32(8)) * jnp.float32(2.0 ** -24)
    u2 = _u24_to_f32(b1 >> jnp.uint32(8)) * jnp.float32(2.0 ** -24)
    u1 = u1 + jnp.float32(2.0 ** -25)
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(u1))
    return r * jnp.cos(jnp.float32(2.0 * math.pi) * u2)


def leaf_seed(key_t: jax.Array, path: str) -> jax.Array:
    """uint32[2] Threefry key for one leaf: (key_t[0] ^ path_hash, key_t[1]).

    The path hash is the same stable 31-bit digest used by fold_in_path, so
    per-leaf streams stay order- and mesh-independent (DESIGN §3).
    """
    kd = jax.random.key_data(key_t).astype(jnp.uint32)
    return kd.at[0].set(kd[0] ^ jnp.uint32(_path_hash(path)))


def _tile_coords(bm: int, bn: int, base_ref):
    """Global (rows, cols) uint32 coordinate grids for the current tile.

    ``base_ref`` holds the global coordinates of this array's (0, 0) element
    — zeros for an unsharded leaf, the shard origin under shard_map — so the
    stream stays a function of the *global* element under any mesh layout.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    rows = base_ref[0] + i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = base_ref[1] + j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    return rows.astype(jnp.uint32), cols.astype(jnp.uint32)


def _seed_words(seed_ref):
    # Mosaic bitcasts vectors only; a same-width int32 -> uint32 convert of
    # the SMEM scalar keeps the bits (two's-complement wrap), like the
    # bitcast in _as_i32_seed that put them there.
    return seed_ref[0, 0].astype(jnp.uint32), seed_ref[0, 1].astype(jnp.uint32)


def _as_i32_seed(seed: jax.Array) -> jax.Array:
    # int32[1, 2]: under vmap (stacked leaves) the batched SMEM block is
    # (squeezed, 1, 2), whose trailing dims equal the array's — the only
    # block shape Mosaic accepts for so small an array.
    seed = jax.lax.bitcast_convert_type(seed.astype(jnp.uint32), jnp.int32)
    return seed.reshape(1, 2)


# ---------------------------------------------------------------------------
# Perturb:  W ← W + scale·z,  z generated on-chip
# ---------------------------------------------------------------------------


def _noise_perturb_kernel(
    seed_ref, scale_ref, base_ref, w_ref, o_ref, *, probes, bm, bn, barrier
):
    k0, k1 = _seed_words(seed_ref)
    rows, cols = _tile_coords(bm, bn, base_ref)
    wf = w_ref[...].astype(jnp.float32)
    for idx, probe in enumerate(probes):
        # round-trip through the VMEM output tile between deltas (the
        # rounding boundary of the replaced HBM pass): a multi-probe chain
        # is bitwise identical to the separate passes.  Interpret mode has
        # no real store boundary, so each delta — z generation included —
        # runs inside its own fence branch (kernels/fence.py) and compiles
        # identically no matter how the schedule groups or consumes it.
        if barrier:
            zero = fence.data_zero(wf)
            sc = scale_ref[idx] + zero

            def delta(wf=wf, sc=sc, probe=probe):
                z = counter_normal(k0, k1, rows, cols, probe)
                return (wf + sc * z).astype(o_ref.dtype)

            val = fence.fenced(zero, delta, lambda wf=wf: wf.astype(o_ref.dtype))
        else:
            z = counter_normal(k0, k1, rows, cols, probe)
            val = (wf + scale_ref[idx] * z).astype(o_ref.dtype)
        o_ref[...] = val
        wf = o_ref[...].astype(jnp.float32)


def _base_arr(base) -> jax.Array:
    """Normalize the global (row0, col0) shard origin to an int32[2] array."""
    if base is None:
        return jnp.zeros((2,), jnp.int32)
    return jnp.asarray(base, jnp.int32).reshape(2)


@functools.partial(jax.jit, static_argnames=("probe", "bm", "bn", "interpret"))
def noise_perturb(
    w: jax.Array,        # [m, n]
    seed: jax.Array,     # uint32[2] (leaf_seed)
    scale: jax.Array | float,        # scalar, or [k] matching a probe tuple
    *,
    base: jax.Array | None = None,   # int32[2] global (row0, col0) of w[0, 0]
    probe: int | tuple[int, ...] = 0,   # static probe id(s) — a tuple is the
    #                                     dual-draw chained-bridge variant
    bm: int = 256,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    m, n = w.shape
    bm = min(bm, m)
    bn = min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    probes = probe if isinstance(probe, tuple) else (probe,)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(-1)
    assert scale_arr.shape[0] in (1, len(probes)), (scale_arr.shape, probes)
    if scale_arr.shape[0] != len(probes):
        scale_arr = jnp.broadcast_to(scale_arr, (len(probes),))
    return pl.pallas_call(
        functools.partial(
            _noise_perturb_kernel, probes=probes, bm=bm, bn=bn,
            barrier=interpret,
        ),
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(_as_i32_seed(seed), scale_arr, _base_arr(base), w)


# ---------------------------------------------------------------------------
# Update:  g = mean_i κ_i z_i in-kernel, then the optimizer rule
# ---------------------------------------------------------------------------


def _noise_update_kernel(*refs, variant, q, restore_probe, bm, bn, barrier):
    seed_ref, hyp_ref, kap_ref, base_ref = refs[0], refs[1], refs[2], refs[3]
    k0, k1 = _seed_words(seed_ref)
    rows, cols = _tile_coords(bm, bn, base_ref)
    w_ref = refs[4]
    o_w_ref = refs[5 if variant == "sgd" else (6 if variant == "momentum" else 7)]
    wf = w_ref[...].astype(jnp.float32)
    if restore_probe is not None:
        # restore-into-update: replay the restore delta(s) — +hyp[5+i]·z_pᵢ
        # for each probe in the (static) chain — each round-tripped through
        # the VMEM output tile, the same rounding the separate restore
        # passes had, so the chained step stays bitwise identical.  In
        # interpret mode each delta runs in its own fence branch, exactly
        # like _noise_perturb_kernel's, so the replay matches the perturb
        # passes it undoes bit for bit (kernels/fence.py).  A probe-parallel
        # step hands the full 3q-delta trajectory-restore chain here; the
        # sequential chained step hands the single trailing (+ρ, q−1) delta.
        rps = restore_probe if isinstance(restore_probe, tuple) else (restore_probe,)
        for idx, rp in enumerate(rps):
            if barrier:
                zero = fence.data_zero(wf)
                rsc = hyp_ref[5 + idx] + zero

                def rdelta(wf=wf, rsc=rsc, rp=rp):
                    zr = counter_normal(k0, k1, rows, cols, rp)
                    return (wf + rsc * zr).astype(o_w_ref.dtype)

                val = fence.fenced(
                    zero, rdelta, lambda wf=wf: wf.astype(o_w_ref.dtype)
                )
            else:
                zr = counter_normal(k0, k1, rows, cols, rp)
                val = (wf + hyp_ref[5 + idx] * zr).astype(o_w_ref.dtype)
            o_w_ref[...] = val
            wf = o_w_ref[...].astype(jnp.float32)

    def optimizer(wf=wf, zero=None):
        # probe mean + the optimizer rule; laundered hyperparameters under
        # the fence so sequential and probe-parallel steps compile this
        # tail identically (the kappa vectors they feed in arrive by
        # different data paths — accumulated vs psum'd — and must not
        # perturb the codegen of the shared math)
        launder = zero if zero is not None else jnp.float32(0)
        g = (kap_ref[0] + launder) * counter_normal(k0, k1, rows, cols, 0)
        for p in range(1, q):
            g = g + (kap_ref[p] + launder) * counter_normal(k0, k1, rows, cols, p)
        g = g * (jnp.float32(1.0 / q) + launder)
        lr = hyp_ref[0] + launder
        # decoupled weight decay folded into the same pass: W ← decay·W − lr·…
        # (decay ≡ 1.0 when cfg.weight_decay == 0 — an exact f32 identity)
        decay = hyp_ref[4] + launder
        if variant == "sgd":
            return ((decay * wf - lr * g).astype(o_w_ref.dtype),)
        if variant == "momentum":
            m_ref = refs[5]
            b1 = hyp_ref[1] + launder
            m_new = b1 * m_ref[...] + (1.0 - b1) * g
            return ((decay * wf - lr * m_new).astype(o_w_ref.dtype), m_new)
        m_ref, v_ref = refs[5], refs[6]
        b1, b2 = hyp_ref[1] + launder, hyp_ref[2] + launder
        eps = hyp_ref[3] + launder
        m_new = b1 * m_ref[...] + (1.0 - b1) * g
        v_new = b2 * v_ref[...] + (1.0 - b2) * g * g
        upd = m_new * jax.lax.rsqrt(v_new + eps)
        return ((decay * wf - lr * upd).astype(o_w_ref.dtype), m_new, v_new)

    if barrier:
        zero = fence.data_zero(wf)

        def fallback(wf=wf):
            outs = [wf.astype(o_w_ref.dtype)]
            if variant in ("momentum", "adam"):
                outs.append(refs[5][...].astype(jnp.float32))
            if variant == "adam":
                outs.append(refs[6][...].astype(jnp.float32))
            return tuple(outs)

        outs = fence.fenced(
            zero, lambda wf=wf, zero=zero: optimizer(wf, zero), fallback
        )
    else:
        outs = optimizer()
    if variant == "sgd":
        refs[5][...] = outs[0]
    elif variant == "momentum":
        refs[6][...] = outs[0]
        refs[7][...] = outs[1]
    else:
        refs[7][...] = outs[0]
        refs[8][...] = outs[1]
        refs[9][...] = outs[2]


@functools.partial(
    jax.jit, static_argnames=("variant", "restore_probe", "bm", "bn", "interpret")
)
def noise_update(
    w: jax.Array,                 # [m, n]
    seed: jax.Array,              # uint32[2]
    kappas: jax.Array,            # [q] f32 — q static via shape
    hyp: jax.Array,               # [5+k] f32: lr, beta1, beta2, eps, decay,
    #                               restore scale(s) (ρ…, matching the
    #                               restore_probe chain; k=1 when scalar)
    m_buf: jax.Array | None = None,   # [m, n] f32 (momentum/adam)
    v_buf: jax.Array | None = None,   # [m, n] f32 (adam)
    *,
    base: jax.Array | None = None,    # int32[2] global (row0, col0) of w[0, 0]
    variant: str = "sgd",
    restore_probe: int | tuple[int, ...] | None = None,  # static: fold the
    #   +hyp[5+i]·z_probeᵢ restore delta(s) in (tuple = restore chain)
    bm: int = 256,
    bn: int = 512,
    interpret: bool = False,
):
    """Fused q-probe mean + optimizer update; returns (w', m'?, v'?).

    The state buffers ride the same grid as W (one HBM round-trip each,
    aliased in-place); z for every probe is regenerated on-chip.  hyp[4] is
    the decoupled weight-decay factor (1 − lr·wd, 1.0 for no decay) applied
    to W in the same fused pass; with ``restore_probe`` set the kernel first
    adds back that probe's +hyp[5]·z (the chained restore-into-update — one
    extra on-chip draw, zero extra HBM traffic).
    """
    m, n = w.shape
    bm = min(bm, m)
    bn = min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    q = kappas.shape[0]
    assert q < MAX_PROBES, q
    if restore_probe is not None:
        rps = restore_probe if isinstance(restore_probe, tuple) else (restore_probe,)
        assert all(rp < MAX_PROBES for rp in rps), rps

    tile = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    operands = [_as_i32_seed(seed), hyp.astype(jnp.float32),
                kappas.astype(jnp.float32), _base_arr(base), w]
    in_specs = [smem, smem, smem, smem, tile]
    out_shapes = [jax.ShapeDtypeStruct((m, n), w.dtype)]
    aliases = {4: 0}
    if variant in ("momentum", "adam"):
        operands.append(m_buf)
        in_specs.append(tile)
        out_shapes.append(jax.ShapeDtypeStruct((m, n), jnp.float32))
        aliases[5] = 1
    if variant == "adam":
        operands.append(v_buf)
        in_specs.append(tile)
        out_shapes.append(jax.ShapeDtypeStruct((m, n), jnp.float32))
        aliases[6] = 2
    out = pl.pallas_call(
        functools.partial(
            _noise_update_kernel, variant=variant, q=q,
            restore_probe=restore_probe, bm=bm, bn=bn, barrier=interpret,
        ),
        grid=(m // bm, n // bn),
        in_specs=in_specs,
        out_specs=[tile] * len(out_shapes),
        out_shape=out_shapes,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)
    return tuple(out)


# ---------------------------------------------------------------------------
# SubZO:  W ← W + scale·(U·Σ·Vᵀ) — tile-resident Z with a Σ core
# ---------------------------------------------------------------------------


def _subzo_kernel(scale_ref, w_ref, u_ref, v_ref, s_ref, o_ref, *, k, r, barrier):
    u = u_ref[...].astype(jnp.float32)          # [bm, r]
    v = v_ref[...].astype(jnp.float32)          # [bn, r]
    s_all = s_ref[...].astype(jnp.float32)      # [k·r, r]
    wf = w_ref[...].astype(jnp.float32)
    for s in range(k):
        # per-step SMEM decay + a VMEM-tile round-trip between deltas; in
        # interpret mode each delta runs in its own fence branch with
        # laundered scalars (kernels/fence.py, same shape as tezo_perturb):
        # the chained pass stays bitwise identical to the standalone passes
        # it replaces under any grouping
        if barrier:
            zero = fence.data_zero(wf)
            d = scale_ref[k + s] + zero
            sc = scale_ref[s] + zero
            sig = s_all[s * r : (s + 1) * r, :] + zero

            def delta(wf=wf, d=d, sc=sc, sig=sig):
                us = jax.lax.dot_general(
                    u, sig, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                # [bm, r]
                z = jax.lax.dot_general(
                    us, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                # [bm, bn]
                return (d * wf + sc * z).astype(o_ref.dtype)

            val = fence.fenced(zero, delta, lambda wf=wf: wf.astype(o_ref.dtype))
        else:
            sig = s_all[s * r : (s + 1) * r, :]  # [r, r]
            us = jax.lax.dot_general(
                u, sig, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                    # [bm, r]
            z = jax.lax.dot_general(
                us, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                    # [bm, bn]
            val = (scale_ref[k + s] * wf + scale_ref[s] * z).astype(o_ref.dtype)
        o_ref[...] = val
        wf = o_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def subzo_perturb(
    w: jax.Array,       # [m, n]
    u: jax.Array,       # [m, r]
    v: jax.Array,       # [n, r]
    sigma: jax.Array,   # [r, r] f32, or [k, r, r] for a k-delta chain
    scale: jax.Array | float,          # scalar, or [k] matching sigma
    decay: jax.Array | float = 1.0,
    *,
    bm: int = 256,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """SubZero's Z = U·Σ·Vᵀ, fused like tezo_perturb: the [bm,r]·[r,r]·[r,bn]
    chain runs on the MXU against the resident W tile, so Z (and U·Σ) never
    reach HBM.  ``decay`` (1 − lr·wd on the update touch, 1.0 otherwise)
    folds decoupled weight decay into the same pass.  A stacked ``sigma``
    [k, r, r] with per-delta ``scale`` [k] applies the perturbation chain's
    merged transitions (bridge / restore-into-update) in one W round-trip;
    decay applies to the last delta only (the update touch)."""
    m, n = w.shape
    r = u.shape[-1]
    bm = min(bm, m)
    bn = min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    sigmas = sigma.reshape((-1, r, r))
    k = sigmas.shape[0]
    scales = jnp.asarray(scale, jnp.float32).reshape(-1)
    assert scales.shape[0] in (1, k), (scales.shape, k)
    if scales.shape[0] != k:
        scales = jnp.broadcast_to(scales, (k,))
    # [scale_0..scale_{k-1}, decay_0..decay_{k-1}]: decay on the final delta
    # only, as an SMEM value per step (see _subzo_kernel)
    scale_arr = jnp.concatenate([
        scales,
        jnp.ones((k - 1,), jnp.float32),
        jnp.asarray(decay, jnp.float32).reshape(1),
    ])
    return pl.pallas_call(
        functools.partial(_subzo_kernel, k=k, r=r, barrier=interpret),
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
            pl.BlockSpec((k * r, r), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(scale_arr, w, u, v, sigmas.reshape((k * r, r)))
