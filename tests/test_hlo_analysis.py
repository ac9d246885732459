"""The HLO-text cost analyzer that powers §Roofline: calibration against
XLA's own cost_analysis on loop-free graphs, and trip-count correctness on
scanned graphs (where XLA undercounts and we must not)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo, roofline_terms


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_loopfree_flops_match_xla():
    def f(w, x):
        return jnp.mean(jax.nn.relu(x @ w) ** 2)

    c = _compile(
        f,
        jax.ShapeDtypeStruct((256, 512), jnp.float32),
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
    )
    xla = c.cost_analysis()
    mine = analyze_hlo(c.as_text(), 1)
    assert abs(mine.flops / max(xla["flops"], 1) - 1.0) < 0.05
    assert 0.5 < mine.bytes_raw / xla["bytes accessed"] < 2.0


def test_scan_trip_count_multiplied():
    L, B, D = 9, 32, 64

    def f(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), None

        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y)

    c = _compile(
        f,
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
        jax.ShapeDtypeStruct((B, D), jnp.float32),
    )
    mine = analyze_hlo(c.as_text(), 1)
    expected = 2.0 * B * D * D * L
    assert abs(mine.flops / expected - 1.0) < 0.05, (mine.flops, expected)


def test_nested_scan_multiplies_through():
    Lo, Li, D = 4, 6, 32

    def f(ws, x):
        def outer(c, w_outer):
            def inner(ci, _):
                return jnp.tanh(ci @ w_outer), None

            c2, _ = jax.lax.scan(inner, c, None, length=Li)
            return c2, None

        y, _ = jax.lax.scan(outer, x, ws)
        return jnp.sum(y)

    c = _compile(
        f,
        jax.ShapeDtypeStruct((Lo, D, D), jnp.float32),
        jax.ShapeDtypeStruct((8, D), jnp.float32),
    )
    mine = analyze_hlo(c.as_text(), 1)
    expected = 2.0 * 8 * D * D * Lo * Li
    assert abs(mine.flops / expected - 1.0) < 0.1, (mine.flops, expected)


def test_roofline_terms_structure():
    t = roofline_terms(197e12, 819e9 * 2, 0.0)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(2.0)
    assert t["dominant"] == "memory_s"
    assert t["roofline_fraction"] == pytest.approx(0.5)
    t2 = roofline_terms(197e12, 819e9, 50e9 * 3)
    assert t2["dominant"] == "collective_s"
