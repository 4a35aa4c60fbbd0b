"""Multi-objective (Pareto) offload search: NSGA selection primitives,
the latency × energy × transfer objective models, front surfacing through
``OffloadResult``, per-objective surrogate fits, and the guarantee that the
single-objective path is bit-identical to the pre-Pareto GA."""
import math

import pytest

from hypothesis import given, settings, strategies as st
from repro.core import Evaluation, GAConfig, OffloadConfig, Offloader
from repro.core import objectives as objmod
from repro.core.ga import (crowding_distances, dominates, non_dominated_sort,
                           pareto_front)
from repro.core.genes import EXTENDED_ALPHABET, coding_from_graph
from repro.core.ir import Region, RegionGraph

from test_offload_api import _det_fitness, _ir_graph

INF = float("inf")


# ---------------------------------------------------------------------------
# synthetic mixed-destination workload: GPU genes cut latency but burn watts,
# CPU genes are slow-and-cool, the fpga_stub adds modeled seconds at low
# watts — so a genuine latency/energy trade-off exists on CPU-only CI
# ---------------------------------------------------------------------------


def _synth_graph(n: int = 5) -> RegionGraph:
    regions = [Region(f"r{i}", "loop", uses=frozenset({f"v{i}"}),
                      defs=frozenset({f"v{i}"}), offloadable=True,
                      alternatives=("ref", "kernel"), trip_count=2 + i)
               for i in range(n)]
    return RegionGraph(regions, "ir", "pareto_synth")


def _speedup_fitness(values) -> Evaluation:
    # each GPU gene shaves a deterministic slice off the wall clock; the
    # pipeline charges the fpga_stub's modeled seconds on top of this
    t = 1.0 - 0.12 * sum(int(v) == 1 for v in values)
    return Evaluation(tuple(values), t, True)


def _mo_config(**over):
    ga = over.pop("ga", GAConfig(population=8, generations=3, seed=0,
                                 objectives=objmod.OBJECTIVES))
    over.setdefault("fitness_fn", _speedup_fitness)
    over.setdefault("destinations", EXTENDED_ALPHABET)
    return OffloadConfig(frontend="ir", ga=ga, **over)


# ---------------------------------------------------------------------------
# dominance + sorting primitives
# ---------------------------------------------------------------------------


def test_dominates_basics():
    assert dominates((1.0, 2.0), (2.0, 2.0))
    assert not dominates((2.0, 2.0), (1.0, 2.0))
    assert not dominates((1.0, 2.0), (1.0, 2.0))      # equal: neither wins
    assert not dominates((1.0, 3.0), (3.0, 1.0))      # trade-off: neither
    assert not dominates((INF, INF), (INF, INF))      # invalid points are
    assert dominates((1.0, 1.0), (INF, INF))          # mutually neutral but
                                                      # dominated by any real


def test_non_dominated_sort_partitions_and_layers():
    pts = [(1.0, 1.0), (2.0, 2.0), (0.5, 3.0), (3.0, 0.5), (2.5, 2.5)]
    fronts = non_dominated_sort(pts)
    assert sorted(i for f in fronts for i in f) == list(range(len(pts)))
    assert sorted(fronts[0]) == [0, 2, 3]
    assert pareto_front(pts) == [0, 2, 3]
    # each later-front point is dominated by someone one layer up
    for k in range(1, len(fronts)):
        for j in fronts[k]:
            assert any(dominates(pts[i], pts[j]) for i in fronts[k - 1])


def test_crowding_preserves_extremes():
    assert crowding_distances([]) == []
    assert crowding_distances([(1.0, 2.0)]) == [INF]
    assert crowding_distances([(1.0, 2.0), (2.0, 1.0)]) == [INF, INF]
    d = crowding_distances([(0.0, 2.0), (1.0, 1.0), (2.0, 0.0)])
    assert d[0] == INF and d[2] == INF            # per-axis boundary points
    assert d[1] == pytest.approx(2.0)             # normalized gap sum


_VEC_SETS = st.integers(1, 4).flatmap(
    lambda m: st.lists(
        st.tuples(*[st.floats(0, 100, allow_nan=False)] * m),
        min_size=1, max_size=12))


@given(_VEC_SETS)
@settings(max_examples=60, deadline=None)
def test_dominance_trichotomy_and_sort_partition(pts):
    """For every pair exactly one of {a dom b, b dom a, neither} holds, no
    point dominates itself, and the sort is a partition whose first front
    is exactly the non-dominated set."""
    for a in pts:
        assert not dominates(a, a)
        for b in pts:
            assert not (dominates(a, b) and dominates(b, a))
    fronts = non_dominated_sort(pts)
    seen = sorted(i for f in fronts for i in f)
    assert seen == list(range(len(pts)))
    front0 = set(fronts[0])
    for i in range(len(pts)):
        dominated = any(dominates(pts[j], pts[i])
                        for j in range(len(pts)) if j != i)
        assert (i in front0) == (not dominated)


# ---------------------------------------------------------------------------
# objective models
# ---------------------------------------------------------------------------


def test_modeled_energy_orders_destinations_by_watts():
    graph = _synth_graph()
    coding = coding_from_graph(graph, destinations=EXTENDED_ALPHABET)
    n = coding.length
    cpu = objmod.modeled_energy_j(graph, coding, (0,) * n, 1.0)
    gpu = objmod.modeled_energy_j(graph, coding, (1,) * n, 1.0)
    assert cpu == pytest.approx(65.0)             # all-host second at 65 W
    assert gpu > cpu                              # hot silicon costs joules
    assert objmod.modeled_energy_j(graph, coding, (0,) * n, INF) == INF
    assert objmod.modeled_energy_j(graph, coding, (0,) * n, -1.0) == INF


def test_objective_values_prefers_measured_detail_fields():
    graph = _synth_graph()
    coding = coding_from_graph(graph, destinations=EXTENDED_ALPHABET)
    bits = (0,) * coding.length
    ev = Evaluation(bits, 1.0, True,
                    {"energy_j": 123.0, "transfer_bytes": 7.0})
    vals = objmod.objective_values(ev, graph, coding)
    assert vals == (1.0, 123.0, 7.0)
    # invalid evaluations map to all-inf (mutually neutral, never selected)
    bad = Evaluation(bits, 1.0, False)
    assert objmod.objective_values(bad, graph, coding) == (INF, INF, INF)
    with pytest.raises(ValueError):
        objmod.objective_values(ev, graph, coding, objectives=("carbon",))


def test_annotate_objectives_stamps_without_overwriting():
    graph = _synth_graph()
    coding = coding_from_graph(graph, destinations=EXTENDED_ALPHABET)
    ann = objmod.annotate_objectives(graph, coding)
    bits = (1,) * coding.length
    ev = ann(Evaluation(bits, 0.5, True))
    assert ev.detail["energy_j"] == pytest.approx(
        objmod.modeled_energy_j(graph, coding, bits, 0.5))
    assert ev.detail["transfer_bytes"] == pytest.approx(
        objmod.static_transfer_bytes(graph, coding, bits))
    # a power-instrumented fitness's own measurement always wins
    ev2 = ann(Evaluation(bits, 0.5, True, {"energy_j": 9.0}))
    assert ev2.detail["energy_j"] == 9.0
    # invalid measurements pass through untouched
    bad = Evaluation(bits, 0.5, False)
    assert ann(bad) is bad


# ---------------------------------------------------------------------------
# the search: mixed-destination Pareto front with a real trade-off
# ---------------------------------------------------------------------------


def test_multi_objective_search_returns_tradeoff_front(tmp_path):
    off = Offloader(_mo_config(
        ga=GAConfig(population=8, generations=3, seed=0,
                    objectives=objmod.OBJECTIVES,
                    cache_dir=str(tmp_path))))
    ctx = off.prepare(_synth_graph())
    res = off.search(ctx)

    front = res.front
    assert len(front) >= 2
    assert res.summary()["front_size"] == len(front)
    pts = [objmod.objective_values(ev, res.graph, res.coding)
           for ev in front]
    for i, a in enumerate(pts):          # the front is pairwise non-dominated
        for j, b in enumerate(pts):
            assert i == j or not dominates(a, b), (front[i], front[j])

    lat = res.operating_point("latency")
    en = res.operating_point("energy")
    assert lat.bits != en.bits
    lat_v = objmod.objective_values(lat, res.graph, res.coding)
    en_v = objmod.objective_values(en, res.graph, res.coding)
    # energy-optimal measurably trades latency for joules, and vice versa
    assert en_v[1] < lat_v[1] and en_v[0] > lat_v[0]
    assert lat.bits == res.best.bits     # best stays the latency winner
    with pytest.raises(ValueError):
        res.operating_point("carbon")

    rows = res.front_summary()
    assert len(rows) == len(front)
    for row in rows:
        assert set(row) == {"bits", "latency_s", "energy_j",
                            "transfer_bytes"}
        assert all(math.isfinite(row[k]) for k in
                   ("latency_s", "energy_j", "transfer_bytes"))

    # per-objective ridge fits landed in the cache beside the latency fit
    from repro.core.surrogate import load_fit
    for obj in ("energy", "transfer"):
        fit = load_fit(str(tmp_path), ctx.fingerprint, objective=obj)
        assert fit is not None and fit["objective"] == obj


def test_single_objective_path_is_unchanged_and_deterministic(tmp_path):
    # an explicit 1-tuple objectives config takes the exact same code path
    # as the default: same RNG stream, same best, same history
    runs = []
    for objectives in (("latency",), ("latency",), objmod.OBJECTIVES):
        cfg = _mo_config(ga=GAConfig(population=8, generations=3, seed=0,
                                     objectives=objectives))
        res = Offloader(cfg).plan(_synth_graph())
        runs.append(res)
    a, b, multi = runs
    assert a.best.bits == b.best.bits
    assert a.ga.history == b.ga.history
    # single-objective searches report a one-point "front": the best
    assert [ev.bits for ev in a.front] == [a.best.bits]
    assert "front_size" not in a.ga.history[-1]
    # the multi run tracked front growth per generation
    assert all(e["front_size"] >= 1 for e in multi.ga.history)


def test_single_objective_matches_default_alphabet_fixture():
    # the tier-1 fixture config (binary alphabet, _det_fitness) must search
    # identically whether or not the objectives field is spelled out
    base = OffloadConfig(frontend="ir", fitness_fn=_det_fitness,
                         ga=GAConfig(population=6, generations=2, seed=0))
    spelled = OffloadConfig(frontend="ir", fitness_fn=_det_fitness,
                            ga=GAConfig(population=6, generations=2, seed=0,
                                        objectives=("latency",)))
    ra = Offloader(base).plan(_ir_graph())
    rb = Offloader(spelled).plan(_ir_graph())
    assert ra.best.bits == rb.best.bits
    assert ra.ga.history == rb.ga.history
