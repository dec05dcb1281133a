"""Trip-count-aware HLO cost analysis: validated against XLA's own
cost_analysis on scan-free modules, and against known trip counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_dot_flops_match_xla_no_scan():
    def fn(a, b):
        return (a @ b).sum()

    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    c = _compiled(fn, a, b)
    got = hlo_cost.analyze(c.as_text())
    want = 2 * 128 * 256 * 64
    assert got.flops == pytest.approx(want, rel=0.02)
    xla = c.cost_analysis()
    assert got.dot_flops_uncorrected == pytest.approx(
        float(xla["flops"]), rel=0.05)


def test_scan_trip_count_multiplies():
    def fn(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=7)
        return h.sum()

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = _compiled(fn, x, w)
    got = hlo_cost.analyze(c.as_text())
    per_iter = 2 * 32 * 64 * 64
    assert got.flops == pytest.approx(7 * per_iter, rel=0.05)
    # XLA's own count misses the trip count
    assert float(c.cost_analysis()["flops"]) == pytest.approx(per_iter,
                                                              rel=0.05)


def test_nested_scan_multiplies():
    def fn(x, w):
        def outer(h, _):
            def inner(g, _):
                return g @ w, None
            g, _ = jax.lax.scan(inner, h, None, length=3)
            return g, None
        h, _ = jax.lax.scan(outer, x, None, length=5)
        return h.sum()

    x = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    c = _compiled(fn, x, w)
    got = hlo_cost.analyze(c.as_text())
    assert got.flops == pytest.approx(15 * 2 * 16 * 32 * 32, rel=0.05)


def test_bytes_proxy_reasonable():
    def fn(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = _compiled(fn, a, b)
    got = hlo_cost.analyze(c.as_text())
    xla_bytes = float(c.cost_analysis()["bytes accessed"])
    assert got.bytes == pytest.approx(xla_bytes, rel=1.0)  # same magnitude


def test_shape_parse():
    b, shapes = hlo_cost._shape_info("(bf16[2,3]{1,0}, f32[4]{0})")
    assert b == 2 * 3 * 2 + 4 * 4
    assert shapes == [("bf16", [2, 3]), ("f32", [4])]
