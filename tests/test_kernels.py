"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(assignment: per-kernel allclose against the ref.py oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def _force_interpret():
    ops.set_interpret(True)
    yield
    ops.set_interpret(None)


PERTURB_SHAPES = [
    (128, 128, 1), (256, 512, 8), (384, 128, 64), (512, 256, 3), (128, 640, 16),
]


@pytest.mark.parametrize("m,n,r", PERTURB_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tezo_perturb_sweep(m, n, r, dtype):
    key = jax.random.PRNGKey(m * 1000 + n + r)
    w = (jax.random.normal(key, (m, n), jnp.float32) * 0.1).astype(dtype)
    u = jax.random.normal(jax.random.fold_in(key, 1), (m, r), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (n, r), jnp.float32)
    tau = jax.random.normal(jax.random.fold_in(key, 3), (r,), jnp.float32)
    for scale in (1e-3, -2e-3):
        got = ops.tezo_perturb(w, u, v, tau, scale)
        want = ref.tezo_perturb_ref(w, u, v, tau, scale)
        atol = 1e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
        )


@pytest.mark.parametrize("m,n,r", [(256, 512, 8), (128, 128, 32), (512, 384, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tezo_adam_sweep(m, n, r, dtype):
    key = jax.random.PRNGKey(r * 7 + m)
    w = (jax.random.normal(key, (m, n), jnp.float32) * 0.1).astype(dtype)
    u = jax.random.normal(jax.random.fold_in(key, 1), (m, r), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (n, r), jnp.float32)
    tm = jax.random.normal(jax.random.fold_in(key, 3), (r,), jnp.float32)
    tv = jnp.abs(jax.random.normal(jax.random.fold_in(key, 4), (r,), jnp.float32))
    got = ops.tezo_adam_update(w, u, v, tm, tv, 1e-4)
    want = ref.tezo_adam_update_ref(w, u, v, tm, tv, 1e-4, 1e-5)
    atol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


def test_rank_padding_matches_unpadded():
    """The MXU rank-padding path (r → multiple of 128, zero-padded) is only
    taken on real TPU, so exercise _pad_rank explicitly against the
    unpadded oracle: zero-padded τ components must contribute nothing to
    either kernel (including tezo_adam's V, where padded τ_V entries are 0
    and the matching M rows are 0, so g is 0 there too)."""
    key = jax.random.PRNGKey(11)
    m, n, r = 128, 256, 24
    w = jax.random.normal(key, (m, n), jnp.float32) * 0.1
    u = jax.random.normal(jax.random.fold_in(key, 1), (m, r), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (n, r), jnp.float32)
    tau = jax.random.normal(jax.random.fold_in(key, 3), (r,), jnp.float32)
    tv = jnp.abs(jax.random.normal(jax.random.fold_in(key, 4), (r,), jnp.float32))

    u_p, v_p, tau_p = ops._pad_rank(u, v, tau)
    assert u_p.shape[-1] == 128 and tau_p.shape[-1] == 128
    got = ops.tezo_perturb(w, u_p, v_p, tau_p, 1e-3, pad_rank=False)
    want = ref.tezo_perturb_ref(w, u, v, tau, 1e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    u_p, v_p, tm_p, tv_p = ops._pad_rank(u, v, tau, tv)
    got = ops.tezo_adam_update(w, u_p, v_p, tm_p, tv_p, 1e-4, pad_rank=False)
    want = ref.tezo_adam_update_ref(w, u, v, tau, tv, 1e-4, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_kernels_batched_leaves():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (3, 128, 256)) * 0.1
    u = jax.random.normal(jax.random.fold_in(key, 1), (3, 128, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (3, 256, 8))
    tau = jax.random.normal(jax.random.fold_in(key, 3), (3, 8))
    got = ops.tezo_perturb(w, u, v, tau, 0.5)
    want = jax.vmap(lambda a, b, c, d: ref.tezo_perturb_ref(a, b, c, d, 0.5))(
        w, u, v, tau
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


_SCALES = (1e-3, -2e-3)


def _tezo_wrapper(kind, w, u, v, taus, tv):
    """One TeZO pass through the ops wrapper (2-D or stacked leaf)."""
    if kind == "perturb_k1":
        return ops.tezo_perturb(w, u, v, taus[..., 0, :], _SCALES[0])
    if kind == "perturb_k2":
        return ops.tezo_perturb(w, u, v, taus, jnp.array(_SCALES), decay=0.999)
    restore = kind == "adam_restore"
    return ops.tezo_adam_update(
        w, u, v, taus[..., 0, :], tv, 1e-4, decay=0.999,
        tau_r=taus[..., 1, :] if restore else None,
        restore_scale=_SCALES[0] if restore else 0.0,
    )


def _tezo_kernel(kind, w, u, v, taus, tv, *, bm, bn):
    """The same pass on one 2-D leaf, straight to the kernel at (bm, bn)."""
    from repro.kernels.tezo_adam import tezo_adam_update as adam
    from repro.kernels.tezo_perturb import tezo_perturb as perturb

    tiles = dict(bm=bm, bn=bn, interpret=True)
    if kind == "perturb_k1":
        return perturb(w, u, v, taus[0], _SCALES[0], 1.0, **tiles)
    if kind == "perturb_k2":
        return perturb(w, u, v, taus, jnp.array(_SCALES), 0.999, **tiles)
    restore = kind == "adam_restore"
    return adam(w, u, v, taus[0], tv, 1e-4, 1e-5, 0.999,
                taus[1] if restore else None,
                _SCALES[0] if restore else 0.0, **tiles)


def _tezo_padded(kind, w, u, v, taus, tv):
    """The path the retiling replaced: the leaf and its factors zero-padded
    to the noise kernels' tile multiple, the kernel at those tiles, the
    tail cropped."""
    m, n = w.shape
    bm, bn, m_pad, n_pad = ops._weight_tiles(m, n)
    out = _tezo_kernel(kind, ops._pad_w(w, m_pad, n_pad),
                       ops._pad_rows(u, m_pad), ops._pad_rows(v, n_pad),
                       taus, tv, bm=bm, bn=bn)
    return ops._crop(out, m, n)


@pytest.mark.parametrize("shape", ["clean", "ragged", "stacked"])
@pytest.mark.parametrize(
    "kind", ["perturb_k1", "perturb_k2", "adam", "adam_restore"])
def test_tezo_tiles_match_padded_path(kind, shape):
    """The TeZO pass kernels on their own blocks — a partial block where
    the block does not divide the leaf — write the same bits as the padded
    copy they replace.  Each case also runs the kernel on blocks that
    divide neither dim of the leaf (partial edge blocks on both axes),
    against the same padded path."""
    # the leaf, and edge blocks that divide neither of its dims
    (m, n), (ebm, ebn) = {"clean": ((256, 512), (48, 384)),
                          "ragged": ((200, 300), (48, 128)),
                          "stacked": ((128, 256), (48, 384))}[shape]
    r = 24
    lead = (2,) if shape == "stacked" else ()
    key = jax.random.PRNGKey(m + n)
    w = (jax.random.normal(key, lead + (m, n)) * 0.1).astype(jnp.bfloat16)
    u = jax.random.normal(jax.random.fold_in(key, 1), lead + (m, r))
    v = jax.random.normal(jax.random.fold_in(key, 2), lead + (n, r))
    taus = jax.random.normal(jax.random.fold_in(key, 3), lead + (2, r))
    tv = jnp.abs(jax.random.normal(jax.random.fold_in(key, 4), lead + (r,)))
    got = _tezo_wrapper(kind, w, u, v, taus, tv)
    if lead:
        slices = [(w[i], u[i], v[i], taus[i], tv[i]) for i in range(lead[0])]
    else:
        slices = [(w, u, v, taus, tv)]
    want = jnp.stack([_tezo_padded(kind, *a) for a in slices])
    edge = jnp.stack([_tezo_kernel(kind, *a, bm=ebm, bn=ebn) for a in slices])
    assert got.shape == w.shape
    want, edge = want.reshape(w.shape), edge.reshape(w.shape)
    bits = lambda a: np.asarray(a.view(jnp.uint16))
    np.testing.assert_array_equal(bits(got), bits(want))
    np.testing.assert_array_equal(bits(edge), bits(want))


@pytest.mark.parametrize("kernel", ["perturb", "adam"])
@pytest.mark.parametrize("r_pad", [128, 256])
@pytest.mark.parametrize("m,n", [(5120, 5120), (5120, 20480), (20480, 5120),
                                 (50272, 5120), (5120, 50272)])
def test_tezo_tiles_at_opt13b_widths(m, n, r_pad, kernel):
    """The block rule, a pure function of what the call observes, gives
    every opt-13b leaf at least 1 MiB of bf16 W per grid step, fetches at
    most 1/8 of the W bytes moved again as factors (the v block on every
    step: r_pad/bm), and fits its working set in the VMEM budget."""
    k = 1
    bm, bn = ops.tezo_tiles(m, n, r_pad, k, kernel, 2)
    ops.tezo_tiles.cache_clear()
    assert ops.tezo_tiles(m, n, r_pad, k, kernel, 2) == (bm, bn)
    assert bm % 16 == 0 and bn % 128 == 0
    w_bytes_moved = 2 * bm * bn * 2
    assert bm * bn * 2 >= 1 << 20
    assert 4 * bn * r_pad <= w_bytes_moved / 8
    assert ops._tezo_working_set(bm, bn, r_pad, k, kernel, 2) <= (
        ops.TEZO_VMEM_BUDGET)


FLASH_CASES = [
    # B, S, T, H, KV, dh, window, q_offset
    (2, 128, 128, 4, 2, 32, 0, 0),
    (1, 256, 256, 4, 4, 64, 0, 0),
    (2, 128, 128, 8, 1, 32, 0, 0),      # MQA
    (1, 128, 128, 4, 2, 32, 48, 0),     # sliding window
    (1, 64, 192, 2, 2, 32, 0, 128),     # cross-chunk offset (q after kv prefix)
]


@pytest.mark.parametrize("B,S,T,H,KV,dh,window,q_offset", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, T, H, KV, dh, window, q_offset, dtype):
    key = jax.random.PRNGKey(S + T + H)
    q = (jax.random.normal(key, (B, S, H, dh), jnp.float32) * 0.3).astype(dtype)
    k = (
        jax.random.normal(jax.random.fold_in(key, 1), (B, T, KV, dh), jnp.float32)
        * 0.3
    ).astype(dtype)
    v = (
        jax.random.normal(jax.random.fold_in(key, 2), (B, T, KV, dh), jnp.float32)
        * 0.3
    ).astype(dtype)
    got = ops.flash_attention(q, k, v, window=window, q_offset=q_offset, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, window=window, q_offset=q_offset)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


def test_flash_block_shapes_sweep():
    """Different BlockSpec tilings must give identical results."""
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (1, 256, 2, 32)) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 256, 2, 32)) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 32)) * 0.3
    want = ref.flash_attention_ref(q, k, v)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        got = ops.flash_attention(q, k, v, bq=bq, bk=bk)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, err_msg=f"bq={bq} bk={bk}"
        )


def test_perturb_kernel_matches_model_path():
    """The kernel must agree with the estimator's jnp perturbation so
    attention_impl/kernel toggles never change semantics."""
    from repro.core import cpd

    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (128, 256)) * 0.1
    fac_tree = cpd.init_factors({"w": w}, key, default_rank=8)
    fac = fac_tree["['w']"]
    tau = cpd.sample_tau(fac, jax.random.PRNGKey(5), "['w']")
    jnp_path = w + 1e-3 * cpd.reconstruct(fac, tau)
    kern = ops.tezo_perturb(w, fac.u, fac.v, tau, 1e-3)
    np.testing.assert_allclose(np.asarray(jnp_path), np.asarray(kern), atol=1e-5)
