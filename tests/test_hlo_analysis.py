"""HLO-text cost analyzer: loop multipliers, dot flops, collective model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.hlo_analysis import analyze_hlo
from repro.roofline import Roofline, peaks_for


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_dot_flops_exact():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    c = _compile(lambda x, y: x @ y, a, b)
    hc = analyze_hlo(c.as_text(), 1)
    assert hc.flops == pytest.approx(2 * 64 * 128 * 32)


def test_while_trip_count_multiplier():
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(x):
        def body(h, _):
            return jnp.tanh(h @ h), ()
        h, _ = jax.lax.scan(body, x, None, length=7)
        return h

    c = _compile(f, a)
    hc = analyze_hlo(c.as_text(), 1)
    assert hc.flops == pytest.approx(7 * 2 * 64 ** 3, rel=0.01)


def test_nested_scan_multipliers_compose():
    a = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    def f(x):
        def outer(h, _):
            def inner(g, _):
                return g @ g, ()
            g, _ = jax.lax.scan(inner, h, None, length=3)
            return g, ()
        h, _ = jax.lax.scan(outer, x, None, length=5)
        return h

    c = _compile(f, a)
    hc = analyze_hlo(c.as_text(), 1)
    assert hc.flops == pytest.approx(15 * 2 * 32 ** 3, rel=0.01)


def test_dynamic_update_slice_counts_slice_not_operand():
    cache = jax.ShapeDtypeStruct((1024, 64), jnp.float32)
    tok = jax.ShapeDtypeStruct((1, 64), jnp.float32)

    def f(c, t):
        return jax.lax.dynamic_update_slice(c, t, (5, 0))

    # donate the cache so the update is in place (no defensive full copy)
    c = jax.jit(f, donate_argnums=(0,)).lower(cache, tok).compile()
    hc = analyze_hlo(c.as_text(), 1)
    # one-row write (2x read+write) — must NOT count the 1024-row cache
    assert hc.bytes < 1024 * 64 * 4 / 4


def test_roofline_terms_and_dominance():
    r = Roofline(flops=197e12 * 0.01, hbm_bytes=819e9 * 0.05,
                 collective_bytes=50e9 * 0.002, n_devices=4,
                 peaks=peaks_for("TPU v5 lite"),
                 model_flops=197e12 * 0.005)
    assert r.compute_s == pytest.approx(0.01)
    assert r.memory_s == pytest.approx(0.05)
    assert r.collective_s == pytest.approx(0.002)
    assert r.dominant == "memory"
    assert r.step_s == pytest.approx(0.05)
    assert r.roofline_fraction == pytest.approx(0.005 / 0.05)
    assert r.useful_flops_ratio == pytest.approx(0.5)


def test_unknown_device_kind_has_no_peaks():
    # a chip with no recorded peaks is an error, never a v5e default
    with pytest.raises(KeyError, match="no peak rates"):
        peaks_for("cpu")


def test_collective_ring_model():
    from repro.hlo_analysis import _ring_bytes
    sz = 1000
    assert _ring_bytes("all-gather", sz, 4) == pytest.approx(750)
    assert _ring_bytes("all-reduce", sz, 4) == pytest.approx(1500)
    assert _ring_bytes("reduce-scatter", sz, 4) == pytest.approx(3000)
    assert _ring_bytes("collective-permute", sz, 4) == pytest.approx(1000)
    assert _ring_bytes("all-reduce", sz, 1) == 0.0
