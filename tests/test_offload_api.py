"""Frontend-conformance suite for the unified offload API.

One parametrized contract runs across every registered frontend: graph
invariants, plan round-trip through ``Offloader.plan`` with a unified
``OffloadResult``, serial==parallel reproducibility at fixed seed, and
multi-destination gene decode.  Plus the satellite surfaces: the ``plan()``
module-level wrapper, alphabet resolution, ``GAConfig.pool`` process-pool
selection, surrogate rank-correlation reporting, and the similarity seed
bank.
"""
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (DEFAULT_ALPHABET, VARIANT_ALPHABET, Evaluation,
                        GAConfig, OffloadConfig, OffloadResult, Offloader,
                        Region, RegionGraph, coding_from_graph,
                        detect_frontend, frontend_names, get_frontend, plan,
                        plan_offload, resolve_alphabet, run_ga)
from repro.core.ga import GAResult
from repro.core.offload import SeedBank, _pattern_db_seed, ga_search
from repro.core.pattern_db import default_db

# ---------------------------------------------------------------------------
# per-frontend fixtures: (target, inputs, OffloadConfig kwargs)
# ---------------------------------------------------------------------------

PY_SRC = """
def app(a, x, n, iters):
    y = np.zeros((n,))
    for it in range(iters):
        y = y + np.tanh(a @ x) * 0.1
    s = 0.0
    for i in range(n):
        s = s + y[i] * y[i]
    return y, s
"""
PY_CONSTS = {"n": 10, "iters": 8}


def _py_inputs():
    rng = np.random.default_rng(0)
    return dict(a=rng.random((10, 10)), x=rng.random(10))


def _traced_fn(x):
    def step(c, t):
        return c * 0.9 + t, c
    _, ys = jax.lax.scan(step, jnp.zeros(()), x)
    return ys * 2.0


def _ir_graph():
    # no callees / vectors, so the pattern DB cannot claim any region and
    # the gene covers all three sites
    regions = [
        Region("outer", "loop", trip_count=50),
        Region("hot", "loop", parent="outer", depth=1,
               uses=frozenset({"a"}), defs=frozenset({"a"}),
               offloadable=True, alternatives=("ref", "kernel"),
               trip_count=10),
        Region("mid", "loop", uses=frozenset({"b"}), defs=frozenset({"b"}),
               offloadable=True, alternatives=("ref", "kernel"),
               trip_count=4),
        Region("cold", "loop", uses=frozenset({"c"}), defs=frozenset({"c"}),
               offloadable=True, alternatives=("ref", "kernel"),
               trip_count=2),
    ]
    return RegionGraph(regions, "ir", "toy")


FRONTEND_CASES = {
    "python_ast": lambda: (PY_SRC, _py_inputs(),
                           {"repeats": 1, "options": {"consts": PY_CONSTS}}),
    "jaxpr": lambda: (_traced_fn, None,
                      {"options": {"example_args": (jnp.ones(8),)}}),
    "module": lambda: (get_config("qwen3_0_6b"), None, {}),
    "ir": lambda: (_ir_graph(), None, {}),
}

ALL_FRONTENDS = sorted(FRONTEND_CASES)


def _config(kwargs, **over) -> OffloadConfig:
    ga = over.pop("ga", GAConfig(population=6, generations=2, seed=0))
    return OffloadConfig(ga=ga, **{**kwargs, **over})


def _det_fitness(values) -> Evaluation:
    # deterministic stand-in verification environment for contracts that
    # need bit-exact reproducibility regardless of wall-clock noise
    t = 1.0 + 0.05 * sum(int(v) * (i + 1) for i, v in enumerate(values))
    return Evaluation(tuple(values), t, True)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_has_all_frontends():
    assert set(ALL_FRONTENDS) <= set(frontend_names())


@pytest.mark.parametrize("name", ALL_FRONTENDS)
def test_detection_maps_target_to_frontend(name):
    target, inputs, kwargs = FRONTEND_CASES[name]()
    assert detect_frontend(target, _config(kwargs)) == name


def test_detection_rejects_unknown_target():
    with pytest.raises(TypeError):
        detect_frontend(12345, OffloadConfig())


# ---------------------------------------------------------------------------
# contract 1: graph invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ALL_FRONTENDS)
def test_graph_invariants(name):
    target, inputs, kwargs = FRONTEND_CASES[name]()
    fe = get_frontend(name)
    cfg = _config(kwargs)
    if hasattr(fe, "normalize_target"):
        target = fe.normalize_target(target, inputs, cfg)
    graph = fe.build_graph(target, inputs, cfg)

    names = [r.name for r in graph.regions]
    assert len(names) == len(set(names)), "region names must be unique"
    for r in graph.regions:
        assert r.kind in ("loop", "call", "block", "stmt")
        if r.parent is not None:
            graph.by_name(r.parent)            # parents must exist
        if r.offloadable:
            assert len(r.alternatives) >= 2, \
                f"offloadable region {r.name} needs (ref, offload) impls"
    assert graph.offloadable(), "every fixture must expose offload sites"
    # the fingerprint is a pure content hash: rebuilding the same target
    # yields the same persistent-cache key
    target2, inputs2, _ = FRONTEND_CASES[name]()
    if hasattr(fe, "normalize_target"):
        target2 = fe.normalize_target(target2, inputs2, cfg)
    graph2 = fe.build_graph(target2, inputs2, cfg)
    assert graph.fingerprint("ctx") == graph2.fingerprint("ctx")


# ---------------------------------------------------------------------------
# contract 2: plan round-trip, one unified result
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ALL_FRONTENDS)
def test_plan_roundtrip_unified_result(name):
    target, inputs, kwargs = FRONTEND_CASES[name]()
    res = plan_offload(target, inputs, config=_config(kwargs))

    assert isinstance(res, OffloadResult)
    assert res.frontend == name
    assert isinstance(res.ga, GAResult)
    assert res.coding.length > 0, "fixtures must leave genes for the GA"
    # decode(best) is embedded in the final pattern verbatim
    decoded = res.coding.decode(res.best.bits)
    for region, impl in decoded.items():
        assert res.pattern[region] == impl
    # destinations cover exactly the gene sites
    assert set(res.destinations) == {s.region for s in res.coding.sites}
    assert set(res.destinations.values()) <= set(res.coding.destinations)
    # result surfaces: baseline/best/savings/verification/artifact
    assert math.isfinite(res.baseline.time_s)
    assert math.isfinite(res.best.time_s)
    assert res.best.time_s <= res.ga.baseline.time_s + 1e-12
    assert res.artifact is not None
    assert res.verification["mode"] in ("measured", "static-cost")
    for key in ("measurements", "measurements_saved", "wall_s",
                "surrogate_rank_corr"):
        assert key in res.savings
    assert res.summary()["frontend"] == name


def test_python_artifact_runs_and_matches_reference():
    target, inputs, kwargs = FRONTEND_CASES["python_ast"]()
    res = plan_offload(target, inputs, config=_config(kwargs))
    out = res.artifact.run(**inputs)
    ref = res.details["program"]  # reference: interpret with no offloads
    from repro.core.frontends.ast_frontend import Executor
    env = Executor(ref, {}, hoist_transfers=False).run(**inputs)
    np.testing.assert_allclose(out["y"], np.asarray(env["y"]), rtol=1e-2)


def test_module_artifact_is_execplan_with_block_claims():
    target, inputs, kwargs = FRONTEND_CASES["module"]()
    res = plan_offload(target, inputs, config=_config(kwargs))
    from repro.models.plan import ExecPlan
    assert isinstance(res.artifact, ExecPlan)
    # the pattern DB matches the model graph's blocks, and the block-pass
    # claims survive into the final plan regardless of the GA
    assert res.block.offloads and res.block.plan_updates
    for field, value in res.block.plan_updates.items():
        assert getattr(res.artifact, field) == value


# ---------------------------------------------------------------------------
# contract 3: serial == parallel reproducibility at fixed seed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ALL_FRONTENDS)
def test_serial_parallel_reproducible(name):
    target, inputs, kwargs = FRONTEND_CASES[name]()

    def plan(workers):
        t, i, k = FRONTEND_CASES[name]()
        cfg = _config(k, fitness_fn=_det_fitness,
                      ga=GAConfig(population=8, generations=3, seed=7,
                                  workers=workers))
        return Offloader(cfg).plan(t, i)

    r_ser = plan(0)
    r_par = plan(4)
    assert r_ser.best.bits == r_par.best.bits
    assert r_ser.best.time_s == r_par.best.time_s
    assert [h["best_time_s"] for h in r_ser.ga.history] == \
        [h["best_time_s"] for h in r_par.ga.history]


# ---------------------------------------------------------------------------
# contract 3b: fixed-seed goldens of Offloader.plan per frontend
# ---------------------------------------------------------------------------

# frontend -> chosen bits, evaluations, best_time_s per generation, with the
# deterministic golden fitness at population 8, generations 3, seed 5
PLAN_GOLDENS = {
    "ir": ((0, 0, 0), 8, [1.5, 1.5, 1.5]),
    "jaxpr": ((0,), 1, [1.0, 1.0, 1.0]),
    "module": ((0, 2, 0, 1), 16, [1.65, 1.65, 1.2]),
    "python_ast": ((0, 0), 4, [1.35, 1.35, 1.35]),
}


@pytest.mark.parametrize("name", ALL_FRONTENDS)
def test_plan_golden_per_frontend(name):
    from test_ga import golden_fitness

    target, inputs, kwargs = FRONTEND_CASES[name]()
    res = Offloader(_config(kwargs, fitness_fn=golden_fitness,
                            ga=GAConfig(population=8, generations=3,
                                        seed=5))).plan(target, inputs)
    bits, evaluations, history = PLAN_GOLDENS[name]
    assert res.best.bits == bits
    assert res.ga.evaluations == evaluations
    assert [h["best_time_s"] for h in res.ga.history] == history


# ---------------------------------------------------------------------------
# contract 4: multi-destination decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ALL_FRONTENDS)
def test_multi_destination_decode(name):
    target, inputs, kwargs = FRONTEND_CASES[name]()

    def plan():
        t, i, k = FRONTEND_CASES[name]()
        cfg = _config(k, destinations=VARIANT_ALPHABET,
                      fitness_fn=_det_fitness,
                      ga=GAConfig(population=8, generations=3, seed=3))
        return Offloader(cfg).plan(t, i)

    r1 = plan()
    assert r1.coding.arity == 3
    assert all(0 <= int(v) < 3 for v in r1.best.bits)

    # the third letter selects each site's second alternative, clamped to
    # the last implementation on a two-entry menu; 0 is the reference
    third = tuple(2 for _ in r1.coding.sites)
    decoded = r1.coding.decode(third)
    for site in r1.coding.sites:
        assert decoded[site.region] == site.impls[min(2, len(site.impls) - 1)]
    assert set(r1.coding.destinations_of(third).values()) == {"gpu_pallas"}
    ref = r1.coding.decode(tuple(0 for _ in r1.coding.sites))
    assert ref == {s.region: s.ref_impl for s in r1.coding.sites}

    # fixed-seed search over the enlarged space is reproducible bit-for-bit
    r2 = plan()
    assert r1.best.bits == r2.best.bits
    assert [h["best_time_s"] for h in r1.ga.history] == \
        [h["best_time_s"] for h in r2.ga.history]


# ---------------------------------------------------------------------------
# satellites: plan() wrapper, alphabet resolution, process pool,
# rank correlation, seed bank
# ---------------------------------------------------------------------------


def test_plan_wrapper_assembles_config_from_kwargs():
    # the module-level one-liner that replaced the retired planner shims
    res = plan(_ir_graph(), fitness_fn=_det_fitness,
               ga=GAConfig(population=6, generations=2, seed=0))
    assert isinstance(res, OffloadResult)
    assert res.frontend == "ir"
    assert set(res.pattern) >= {s.region for s in res.coding.sites}
    # a whole config works too, and both at once is an error
    res2 = plan(_ir_graph(), config=OffloadConfig(
        fitness_fn=_det_fitness,
        ga=GAConfig(population=6, generations=2, seed=0)))
    assert res2.best.bits == res.best.bits
    with pytest.raises(ValueError, match="not both"):
        plan(_ir_graph(), config=OffloadConfig(), repeats=1)


def test_resolve_alphabet_explicit_config_wins():
    cfg = OffloadConfig(destinations=VARIANT_ALPHABET)
    assert resolve_alphabet(cfg, ("cpu", "gpu")) == VARIANT_ALPHABET


def test_resolve_alphabet_falls_back_to_frontend_proposal():
    assert resolve_alphabet(OffloadConfig(), ("cpu", "gpu_fused")) == \
        ("cpu", "gpu_fused")
    assert resolve_alphabet(None, ("cpu", "gpu_fused")) == \
        ("cpu", "gpu_fused")


def test_resolve_alphabet_defaults_when_nothing_given():
    assert resolve_alphabet(None) == DEFAULT_ALPHABET
    assert resolve_alphabet(OffloadConfig(), None) == DEFAULT_ALPHABET


@pytest.mark.parametrize("mesh,host_devices", [
    ("mesh:data:2:batch", 1),
    ("mesh:data:8:batch", 4),
    ("mesh:model:4:feature", 2),
])
def test_resolve_alphabet_refuses_mesh_wider_than_host(monkeypatch, mesh,
                                                       host_devices):
    # a mesh the host cannot build has no measurement: an explicit alphabet
    # naming one is refused before any search, never modelled
    from repro.core import genes

    monkeypatch.setattr(genes, "_PROBED_DEVICE_COUNT", host_devices)
    with pytest.raises(ValueError, match="needs"):
        resolve_alphabet(OffloadConfig(destinations=("cpu", "gpu", mesh)))
    with pytest.raises(ValueError, match="needs"):
        Offloader(OffloadConfig(destinations=("cpu", mesh))).prepare(
            _ir_graph())
    # a mesh that fits the host is accepted
    fits = f"mesh:data:{host_devices}:batch"
    assert resolve_alphabet(OffloadConfig(destinations=("cpu", fits))) == \
        ("cpu", fits)


def test_resolve_alphabet_validates_names():
    with pytest.raises(KeyError):
        resolve_alphabet(OffloadConfig(destinations=("cpu", "nope")))
    # mesh wire names parse on demand and are valid alphabet entries
    assert resolve_alphabet(None, ("cpu", "gpu", "mesh:data:4:batch")) == \
        ("cpu", "gpu", "mesh:data:4:batch")


def test_gaconfig_pool_runs_search_in_processes():
    # "smoke" is the registry's shipped factory; spawn workers rebuild it
    g = _ir_graph()
    cfg = GAConfig(population=6, generations=2, seed=0,
                   pool="smoke", workers=2)
    coding, ga = ga_search(g, None, cfg)
    # same trajectory as the in-process run of the identical fitness
    from repro.core.evaluator import _smoke_fitness_factory
    coding2, ga2 = ga_search(g, _smoke_fitness_factory(),
                             GAConfig(population=6, generations=2, seed=0))
    assert ga.best.bits == ga2.best.bits
    assert ga.best.time_s == ga2.best.time_s


def test_unknown_pool_factory_raises():
    with pytest.raises(KeyError):
        ga_search(_ir_graph(), None,
                  GAConfig(pool="no-such-factory", workers=2))


def test_process_pool_refuses_on_tpu_backend(monkeypatch):
    # a chip belongs to one process: spawned JAX workers would find it taken
    # (or hang), so the pool refuses before starting any
    import jax

    from repro.core.evaluator import ProcessPool
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="chip belongs to this process"):
        ProcessPool("smoke", workers=1)
    with pytest.raises(RuntimeError, match="ProcessPool"):
        ga_search(_ir_graph(), None, GAConfig(pool="smoke", workers=2))


def test_offloader_rejects_pool():
    # the pipeline composes a fitness (block claims, exclusions, destination
    # costs) that spawn workers cannot rebuild from a factory — measuring a
    # different function than the one planned must be an error, not silent
    cfg = OffloadConfig(fitness_fn=_det_fitness,
                        ga=GAConfig(pool="smoke", workers=2))
    with pytest.raises(ValueError, match="Offloader.plan"):
        Offloader(cfg).plan(_ir_graph())


def test_surrogate_rank_corr_reported_by_search():
    g = _ir_graph()
    _, ga = ga_search(g, _det_fitness,
                      GAConfig(population=8, generations=4, seed=1))
    corr = ga.surrogate_rank_corr
    assert math.isfinite(corr) and -1.0 <= corr <= 1.0


def test_seed_bank_neighbor_warm_start(tmp_path):
    g = _ir_graph()
    coding = coding_from_graph(g)
    bank = SeedBank(str(tmp_path))
    bank.record(g, coding, (1, 0, 1))
    seeds = bank.neighbor_seeds(g, coding)
    assert seeds == [(1, 0, 1)]
    # a different frontend's record never leaks in
    g2 = RegionGraph(list(g.regions), "jaxpr", "other")
    assert bank.neighbor_seeds(g2, coding_from_graph(g2)) == []
    # values clamp to the current alphabet
    bank2 = SeedBank(str(tmp_path / "b2"))
    coding3 = coding_from_graph(g, destinations=VARIANT_ALPHABET)
    bank2.record(g, coding3, (2, 0, 2))
    assert bank2.neighbor_seeds(g, coding)[0] == (1, 0, 1)


def test_seed_bank_size_bound_and_lru_eviction(tmp_path):
    g = _ir_graph()
    coding = coding_from_graph(g)
    bank = SeedBank(str(tmp_path), max_records=4)
    # 12 distinct records against a 4-record bound: journal must compact
    for i in range(12):
        bank.record(RegionGraph(list(g.regions), "ir", f"prog{i}"),
                    coding, (i % 2, 0, 1))
    live = bank._live()
    assert len(live) <= 4
    assert [r["source"] for r in live] == ["prog8", "prog9", "prog10",
                                           "prog11"]
    with open(bank.path) as f:
        assert sum(1 for _ in f) <= 2 * 4 + 1   # file itself stays bounded

    # LRU: a touched record outlives contemporaries it was older than
    bank2 = SeedBank(str(tmp_path / "lru"), max_records=3)
    bank2.record(g, coding, (1, 0, 1))                     # the survivor
    for i in range(2):
        bank2.record(RegionGraph(list(g.regions), "ir", f"noise{i}"),
                     coding, (0, 1, 0))
    assert bank2.neighbor_seeds(g, coding, limit=1) == [(1, 0, 1)]  # touch
    bank2.record(RegionGraph(list(g.regions), "ir", "noise2"),
                 coding, (0, 1, 0))
    sources = [r["source"] for r in bank2._live()]
    assert "toy" in sources and "noise0" not in sources    # LRU, not FIFO


def test_seed_bank_cross_destination_mapping(tmp_path):
    # a neighbor's GPU gene (binary alphabet) seeds a search over alphabets
    # that don't contain "gpu": offloaded genes land on the new primary
    # accelerator, reference genes stay reference
    g = _ir_graph()
    bank = SeedBank(str(tmp_path))
    bank.record(g, coding_from_graph(g), (1, 0, 1))        # cpu/gpu record
    variant_coding = coding_from_graph(g, destinations=VARIANT_ALPHABET)
    assert bank.neighbor_seeds(g, variant_coding) == [(1, 0, 1)]
    pallas_coding = coding_from_graph(g, destinations=("cpu", "gpu_pallas"))
    assert bank.neighbor_seeds(g, pallas_coding) == [(1, 0, 1)]
    # and the reverse: a variant-alphabet record seeding a binary search
    bank2 = SeedBank(str(tmp_path / "rev"))
    bank2.record(g, variant_coding, (2, 0, 1))             # pallas/ref/fused
    assert bank2.neighbor_seeds(g, coding_from_graph(g)) == [(1, 0, 1)]


def test_auto_screen_from_prior_rank_corr(tmp_path):
    # search 1 records the surrogate's rank correlation for the program
    # fingerprint; search 2 sees it clear the bar and screens automatically.
    # (6 genes = 64 patterns, so a reseeded search still proposes offspring
    # the first search never measured — the ones screening acts on.)
    g = RegionGraph([
        Region(f"r{i}", "loop", uses=frozenset({f"v{i}"}),
               defs=frozenset({f"v{i}"}), offloadable=True,
               alternatives=("ref", "kernel"), trip_count=2 + i)
        for i in range(6)], "ir", "wide")

    def fit(values):
        # fitness aligned with the transfer-cost surrogate -> high corr
        return Evaluation(tuple(values),
                          1.0 + sum(int(v) * (i + 1)
                                    for i, v in enumerate(values)), True)

    cfg = GAConfig(population=8, generations=4, seed=1,
                   cache_dir=str(tmp_path))
    _, ga1 = ga_search(g, fit, cfg)
    assert ga1.screened_out == 0
    assert math.isfinite(ga1.surrogate_rank_corr)
    assert ga1.surrogate_rank_corr >= cfg.auto_screen_corr

    logs = []
    _, ga2 = ga_search(g, fit, GAConfig(population=8, generations=4, seed=2,
                                        cache_dir=str(tmp_path)),
                       log=logs.append)
    assert ga2.screened_out > 0
    assert any("auto-screen" in line for line in logs)
    # explicit opt-out wins
    _, ga3 = ga_search(g, fit, GAConfig(population=8, generations=4, seed=3,
                                        cache_dir=str(tmp_path),
                                        auto_screen=False))
    assert ga3.screened_out == 0


def test_pattern_db_seed_sets_matched_regions():
    regions = [
        Region("mm", "loop", callees=("np.matmul",), offloadable=True,
               alternatives=("interp", "jit")),
        Region("plain", "loop", offloadable=True,
               alternatives=("interp", "jit")),
    ]
    g = RegionGraph(regions, "python_ast", "seeded")
    coding = coding_from_graph(g)
    seeds = _pattern_db_seed(g, coding, default_db())
    assert seeds == [(1, 0)]


def test_run_ga_seed_injection_measures_seed_first():
    measured = []

    def fit(values):
        measured.append(tuple(values))
        return _det_fitness(values)

    run_ga(4, fit, GAConfig(population=6, generations=1, seed=0),
           seeds=[(1, 0, 1, 0)])
    assert (1, 0, 1, 0) in measured


def test_seed_bank_roundtrips_mesh_alphabets(tmp_path):
    # mesh wire names are ordinary destination names to the bank: a record
    # over a mesh-bearing alphabet seeds the same alphabet verbatim, and
    # cross-alphabet mapping stays name-faithful when the name is present
    g = _ir_graph()
    mesh_alpha = ("cpu", "gpu", "mesh:data:4:batch")
    mesh_coding = coding_from_graph(g, destinations=mesh_alpha)
    bank = SeedBank(str(tmp_path))
    bank.record(g, mesh_coding, (2, 0, 1))
    assert bank.neighbor_seeds(g, mesh_coding) == [(2, 0, 1)]
    # a wider alphabet containing the same mesh name keeps the placement
    wide = coding_from_graph(
        g, destinations=("cpu", "gpu", "gpu_fused", "mesh:data:4:batch"))
    assert bank.neighbor_seeds(g, wide) == [(3, 0, 1)]
    # an alphabet without it degrades to the primary accelerator slot
    assert bank.neighbor_seeds(g, coding_from_graph(g)) == [(1, 0, 1)]
