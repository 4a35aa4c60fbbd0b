"""GA engine invariants: convergence, caching, invalid handling, determinism,
and fixed-seed goldens of the roulette trajectory.  Property-based tests via
hypothesis."""
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


@given(length=st.integers(1, 8), arity=st.integers(2, 4),
       seed=st.integers(0, 100))
@settings(**SETTINGS)
def test_property_best_no_worse_than_seeded_patterns(length, arity, seed):
    # the all-off and all-on patterns are always measured, so the search
    # never returns a plan slower than either (the baseline or offloading
    # everything)
    def fit(bits):
        t = 1.0 + sum(((i * 5 + v * 3) % 7 - 3) * 0.04
                      for i, v in enumerate(bits))
        return Evaluation(bits, t, True)

    res = run_ga(length, fit, GAConfig(population=6, generations=3,
                                       seed=seed), arity=arity)
    assert res.best.time_s <= fit((0,) * length).time_s
    assert res.best.time_s <= fit((1,) * length).time_s


# ---------------------------------------------------------------------------
# goldens: the roulette GA's trajectory at fixed seeds
# ---------------------------------------------------------------------------


def golden_fitness(values) -> Evaluation:
    """Deterministic fitness with a non-trivial optimum over any arity:
    each (site, value) pair adds its own weight, and a chromosome whose
    first and last genes are both 2 fails verification."""
    values = tuple(int(v) for v in values)
    if len(values) >= 2 and values[0] == values[-1] == 2:
        return Evaluation(values, float("inf"), False)
    t = 1.0 + sum(((i * 7 + v * 13) % 11) / 20.0
                  for i, v in enumerate(values))
    return Evaluation(values, t, True)


# (seed, length, arity, population, generations) -> best bits, evaluations,
# per generation (best_time_s, gen_best_time_s, mean_time_s, n_invalid)
RUN_GA_GOLDENS = [
    ((0, 6, 2, 8, 4), (0, 0, 0, 1, 0, 0), 24,
     [(2.15, 2.15, 2.4187499999999997, 0), (2.05, 2.05, 2.275, 0),
      (2.05, 2.05, 2.35625, 0), (1.95, 1.95, 2.3499999999999996, 0)]),
    ((3, 10, 2, 10, 5), (0, 0, 1, 1, 0, 0, 1, 1, 0, 0), 42,
     [(2.95, 2.95, 3.535, 0), (2.85, 2.85, 3.34, 0),
      (2.85, 2.85, 3.3549999999999995, 0), (2.85, 2.85, 3.3699999999999997, 0),
      (2.85, 2.85, 3.3, 0)]),
    ((7, 5, 3, 8, 4), (0, 2, 0, 1, 0), 26,
     [(1.7000000000000002, 1.7000000000000002, 2.2857142857142856, 1),
      (1.7, 1.7, 2.0875, 0), (1.5, 1.5, 1.8125000000000002, 0),
      (1.5, 1.5, 1.8375, 0)]),
    ((11, 8, 3, 12, 3), (0, 0, 0, 1, 0, 0, 2, 0), 32,
     [(2.55, 2.55, 2.9227272727272733, 1), (2.3, 2.3, 2.8136363636363635, 1),
      (2.3, 2.3, 2.804166666666666, 0)]),
]


@pytest.mark.parametrize("setting,best,evaluations,history", RUN_GA_GOLDENS)
def test_run_ga_golden_trajectory(setting, best, evaluations, history):
    seed, length, arity, population, generations = setting
    res = run_ga(length, golden_fitness,
                 GAConfig(population=population, generations=generations,
                          seed=seed), arity=arity)
    assert res.best.bits == best
    assert res.evaluations == evaluations
    assert res.history == [
        {"generation": g, "best_time_s": b, "gen_best_time_s": gb,
         "mean_time_s": m, "n_invalid": inv}
        for g, (b, gb, m, inv) in enumerate(history)]


# ---------------------------------------------------------------------------
# the planner's single-objective search (through Offloader.plan)
# ---------------------------------------------------------------------------


def _synth_graph(n: int = 5):
    from repro.core.ir import Region, RegionGraph
    regions = [Region(f"r{i}", "loop", uses=frozenset({f"v{i}"}),
                      defs=frozenset({f"v{i}"}), offloadable=True,
                      alternatives=("ref", "kernel"), trip_count=2 + i)
               for i in range(n)]
    return RegionGraph(regions, "ir", "synth")


def _speedup_fitness(values) -> Evaluation:
    # each offloaded gene shaves a deterministic slice off the wall clock
    t = 1.0 - 0.12 * sum(int(v) == 1 for v in values)
    return Evaluation(tuple(values), t, True)


def test_single_objective_path_is_unchanged_and_deterministic():
    # the same config searches identically twice (same RNG stream, same
    # best, same history), and the history holds the paper's per-generation
    # entries and nothing else
    from repro.core import OffloadConfig, Offloader

    runs = [Offloader(OffloadConfig(
        frontend="ir", fitness_fn=_speedup_fitness,
        ga=GAConfig(population=8, generations=3, seed=0))).plan(
            _synth_graph()) for _ in range(2)]
    a, b = runs
    assert a.best.bits == b.best.bits
    assert a.ga.history == b.ga.history
    assert all(set(h) == {"generation", "best_time_s", "gen_best_time_s",
                          "mean_time_s", "n_invalid"} for h in a.ga.history)


def test_single_objective_matches_default_alphabet_fixture():
    # the tier-1 fixture config (binary alphabet, _det_fitness) must search
    # identically whether or not the default alphabet is spelled out
    from repro.core import DEFAULT_ALPHABET, OffloadConfig, Offloader
    from test_offload_api import _det_fitness, _ir_graph

    base = OffloadConfig(frontend="ir", fitness_fn=_det_fitness,
                         ga=GAConfig(population=6, generations=2, seed=0))
    spelled = OffloadConfig(frontend="ir", fitness_fn=_det_fitness,
                            destinations=DEFAULT_ALPHABET,
                            ga=GAConfig(population=6, generations=2, seed=0))
    ra = Offloader(base).plan(_ir_graph())
    rb = Offloader(spelled).plan(_ir_graph())
    assert ra.best.bits == rb.best.bits
    assert ra.ga.history == rb.ga.history
