"""GA engine invariants: convergence, caching, invalid handling, determinism.
Property-based tests via hypothesis."""
import math

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.ga import Evaluation, GAConfig, run_ga

SETTINGS = dict(max_examples=20, deadline=None)


def _linear_fitness(weights):
    """Offloading bit i saves weights[i] (can be negative = hurts)."""
    def fit(bits):
        t = 1.0 + sum(w for b, w in zip(bits, weights) if b)
        if t <= 0:
            t = 1e-3
        return Evaluation(bits, t, True)
    return fit


def test_converges_to_known_optimum():
    # bits 0,2 help; bit 1 hurts; bit 3 neutral-negative
    weights = [-0.4, +0.3, -0.35, +0.1]
    res = run_ga(4, _linear_fitness(weights),
                 GAConfig(population=10, generations=12, seed=0))
    assert res.best.bits[0] == 1 and res.best.bits[2] == 1
    assert res.best.bits[1] == 0 and res.best.bits[3] == 0
    assert res.best.time_s == pytest.approx(1.0 - 0.75, abs=1e-9)


def test_baseline_recorded_and_speedup():
    res = run_ga(3, _linear_fitness([-0.2, -0.2, -0.2]),
                 GAConfig(population=8, generations=6, seed=1))
    assert res.baseline is not None
    assert res.baseline.time_s == pytest.approx(1.0)
    assert res.speedup_vs_baseline > 1.5


def test_invalid_patterns_never_win():
    # any pattern with bit 0 set is invalid (verification failure)
    def fit(bits):
        if bits and bits[0] == 1:
            return Evaluation(bits, float("inf"), False)
        t = 1.0 - 0.3 * sum(bits[1:])
        return Evaluation(bits, max(t, 0.01), True)
    res = run_ga(4, fit, GAConfig(population=10, generations=8, seed=2))
    assert res.best.bits[0] == 0
    assert res.best.valid


def test_measurement_cache_no_repeats():
    calls = []

    def fit(bits):
        calls.append(bits)
        return Evaluation(bits, 1.0 + sum(bits) * 0.1, True)

    res = run_ga(3, fit, GAConfig(population=8, generations=10, seed=3))
    # every measured chromosome measured exactly once (paper: patterns are
    # never re-measured)
    assert len(calls) == len(set(calls))
    assert res.evaluations == len(calls)
    assert res.cache_hits > 0  # small space -> revisits happen


def test_deterministic_given_seed():
    fit = _linear_fitness([-0.1, 0.2, -0.3, 0.05, -0.02])
    r1 = run_ga(5, fit, GAConfig(population=8, generations=5, seed=42))
    r2 = run_ga(5, fit, GAConfig(population=8, generations=5, seed=42))
    assert r1.best.bits == r2.best.bits
    assert [h["best_time_s"] for h in r1.history] == \
        [h["best_time_s"] for h in r2.history]


def test_zero_length_genome():
    res = run_ga(0, lambda b: Evaluation(b, 1.0, True), GAConfig())
    assert res.best.bits == ()


@given(length=st.integers(1, 8), seed=st.integers(0, 100))
@settings(**SETTINGS)
def test_property_best_is_min_of_measured(length, seed):
    measured = {}

    def fit(bits):
        t = 1.0 + 0.1 * int(np.dot(bits, 2 ** np.arange(len(bits)))) % 7
        measured[bits] = t
        return Evaluation(bits, t, True)

    res = run_ga(length, fit, GAConfig(population=6, generations=4, seed=seed))
    assert res.best.time_s == min(measured.values())
    assert measured[res.best.bits] == res.best.time_s


@given(length=st.integers(1, 6), seed=st.integers(0, 50))
@settings(**SETTINGS)
def test_property_monotone_history(length, seed):
    def fit(bits):
        return Evaluation(bits, 1.0 + sum(bits) * 0.05, True)
    res = run_ga(length, fit, GAConfig(population=5, generations=5, seed=seed))
    best_times = [h["best_time_s"] for h in res.history]
    assert all(b2 <= b1 + 1e-12 for b1, b2 in zip(best_times, best_times[1:]))
    # all-off seeded: baseline must equal the all-zero measurement
    assert res.baseline.time_s == pytest.approx(1.0)
