"""Journal-fitted surrogate + compile-overlap tests.

The fitted model must demonstrably out-rank the hand formula on a journal
whose per-site effects the formula cannot see, abstain below the record
threshold, persist/reload its coefficients, and plug into ``ga_search``'s
screening selection.  The compile-parallel/time-serial phase must produce
byte-identical Evaluations to serial warm-up (timing-independent
assertions on a deterministic two-phase fitness) and report its savings.
"""
import json
import math
import os

import numpy as np
import pytest

from repro.core.evaluator import Evaluator, transfer_cost_surrogate
from repro.core.ga import Evaluation, GAConfig, run_ga
from repro.core.genes import coding_from_graph
from repro.core.ir import Region, RegionGraph
from repro.core.offload import ga_search, phenotype_key, search_fingerprint
from repro.core.surrogate import (SURROGATE_FIT_FILE, FeatureExtractor,
                                  fit_surrogate, load_fit,
                                  spearman_rank_corr)


def _graph(n=5):
    return RegionGraph([
        Region(f"r{i}", "loop", uses=frozenset({f"v{i}"}),
               defs=frozenset({f"v{i}"}), offloadable=True,
               alternatives=("ref", "kernel"), trip_count=2 + i)
        for i in range(n)], "ir", "surrogate-test")


#: per-site effects the hand formula cannot see: r1's offload is slow,
#: r3's is very fast — transfer counts alone misrank these patterns
_W = (0.05, 0.9, -0.1, -0.6, -0.05)


def _site_effect_fitness(bits):
    t = 1.0 + sum(w * b for w, b in zip(_W, bits))
    return Evaluation(tuple(bits), t, True)


def _seed_journal(cache_dir, fingerprint="fp", n=40, seed=0):
    g = _graph()
    ev = Evaluator(_site_effect_fitness, cache_dir=str(cache_dir),
                   fingerprint=fingerprint)
    rng = np.random.default_rng(seed)
    ev.evaluate_batch([tuple(int(x) for x in rng.integers(0, 2, 5))
                       for _ in range(n)])
    return g


# ---------------------------------------------------------------------------
# spearman helper
# ---------------------------------------------------------------------------


def test_spearman_rank_corr_basics():
    assert spearman_rank_corr([1, 2, 3, 4], [10, 20, 30, 40]) \
        == pytest.approx(1.0)
    assert spearman_rank_corr([1, 2, 3, 4], [40, 30, 20, 10]) \
        == pytest.approx(-1.0)
    assert math.isnan(spearman_rank_corr([1, 2], [1, 2]))       # too few
    assert math.isnan(spearman_rank_corr([1, 1, 1], [1, 2, 3]))  # constant


# ---------------------------------------------------------------------------
# the fit
# ---------------------------------------------------------------------------


def test_fitted_surrogate_outranks_static_on_synthetic_journal(tmp_path):
    g = _seed_journal(tmp_path)
    coding = coding_from_graph(g)
    static = transfer_cost_surrogate(g, coding)
    fit = fit_surrogate(g, coding, str(tmp_path), "fp", prior=static,
                        min_records=10)
    assert fit is not None
    assert math.isfinite(fit.rank_corr)
    # the acceptance criterion: strictly exceeds the hand formula
    assert fit.rank_corr > fit.static_rank_corr
    assert fit.beats_static
    # and it is a usable ranking function over chromosomes
    scores = [fit(bits) for bits in
              [(0, 0, 0, 0, 0), (0, 1, 0, 0, 0), (0, 0, 0, 1, 0)]]
    assert scores[1] > scores[0] > scores[2]  # slow r1 last, fast r3 first


def test_fit_abstains_below_min_records(tmp_path):
    g = _seed_journal(tmp_path, n=4)
    coding = coding_from_graph(g)
    assert fit_surrogate(g, coding, str(tmp_path), "fp",
                         min_records=10) is None
    # and on a journal for a fingerprint that was never measured
    assert fit_surrogate(g, coding, str(tmp_path), "other",
                         min_records=10) is None


def test_fit_ignores_foreign_and_invalid_journal_rows(tmp_path):
    g = _seed_journal(tmp_path, n=20)
    coding = coding_from_graph(g)
    ev = Evaluator(lambda b: Evaluation(tuple(b), float("inf"), False),
                   cache_dir=str(tmp_path), fingerprint="fp2")
    ev.evaluate_batch([(1, 0, 0, 0, 0), (0, 1, 0, 0, 0), (0, 0, 1, 0, 0)])
    assert fit_surrogate(g, coding, str(tmp_path), "fp2",
                         min_records=3) is None   # invalid rows don't count


def test_coefficient_persistence_round_trip(tmp_path):
    g = _seed_journal(tmp_path)
    coding = coding_from_graph(g)
    fit = fit_surrogate(g, coding, str(tmp_path), "fp", min_records=10)
    assert os.path.exists(os.path.join(str(tmp_path), SURROGATE_FIT_FILE))
    rec = load_fit(str(tmp_path), "fp")
    assert rec is not None
    assert rec["n_records"] == fit.n_records
    assert rec["rank_corr"] == pytest.approx(fit.rank_corr)
    assert rec["static_rank_corr"] == pytest.approx(fit.static_rank_corr)
    assert rec["feature_names"] == list(fit.extractor.feature_names)
    assert rec["coefficients"] == pytest.approx(fit.coefficients())
    assert load_fit(str(tmp_path), "unknown") is None
    # refits journal newest-last; load returns the most recent record
    fit2 = fit_surrogate(g, coding, str(tmp_path), "fp", min_records=10)
    rec2 = load_fit(str(tmp_path), "fp")
    assert rec2["n_records"] == fit2.n_records


def test_fit_records_of_an_older_schema_are_ignored_then_refit(tmp_path):
    # a coefficient record persisted under older feature names (a modelled
    # stub-cost feature, another objective's fit) is never read as the
    # current fit: load_fit skips it, and the next fit replaces it
    g = _seed_journal(tmp_path)
    coding = coding_from_graph(g)
    names = list(FeatureExtractor(g, coding, prior=lambda b: 0.0)
                 .feature_names)
    old = names[:7] + ["stub_cost"] + names[7:]
    with open(os.path.join(str(tmp_path), SURROGATE_FIT_FILE), "w") as f:
        for objective in ("latency", "transfer"):
            f.write(json.dumps({
                "fingerprint": "fp", "objective": objective,
                "n_records": 99, "feature_names": old,
                "coef": [1.0] * len(old), "mean": [0.0] * len(old),
                "scale": [1.0] * len(old), "intercept": 0.0}) + "\n")
    assert load_fit(str(tmp_path), "fp") is None
    fit = fit_surrogate(g, coding, str(tmp_path), "fp", min_records=10)
    rec = load_fit(str(tmp_path), "fp")
    assert rec["n_records"] == fit.n_records != 99
    assert rec["feature_names"] == names
    assert "stub_cost" not in rec["coefficients"]


def test_feature_extractor_names_align_with_vector(tmp_path):
    g = _graph()
    coding = coding_from_graph(g)
    fx = FeatureExtractor(g, coding, prior=lambda b: 0.0)
    vec = fx(coding.all_on())
    assert len(vec) == len(fx.feature_names)
    named = dict(zip(fx.feature_names, vec))
    assert named["offload_trips"] > 0          # all-on offloads everything
    assert named["dest1"] == coding.length
    assert named["site0@1"] == 1.0


# ---------------------------------------------------------------------------
# ga_search selection: screening improves with every search
# ---------------------------------------------------------------------------


def test_ga_search_prefers_fitted_surrogate_when_it_ranks_better(tmp_path):
    g = _graph()
    cfg = dict(population=8, generations=5, cache_dir=str(tmp_path))
    _, ga1 = ga_search(g, _site_effect_fitness, GAConfig(seed=0, **cfg))
    assert ga1.surrogate_kind == "static"      # no journal yet at build time
    _, ga2 = ga_search(g, _site_effect_fitness, GAConfig(seed=1, **cfg))
    assert ga2.surrogate_kind == "fitted"
    # the measured (out-of-sample) rank correlation improved materially —
    # deterministic fitness, so this is exact, not luck
    assert ga2.surrogate_rank_corr > max(0.9, ga1.surrogate_rank_corr)
    # the fit was journaled beside search_meta.jsonl for inspection
    fp = search_fingerprint(g, coding_from_graph(g))
    assert load_fit(str(tmp_path), fp) is not None
    # and the evidence record names which surrogate produced it
    with open(os.path.join(str(tmp_path), "search_meta.jsonl")) as f:
        kinds = [json.loads(line).get("kind") for line in f if line.strip()]
    assert "fitted" in kinds


def test_ga_search_fit_opt_out(tmp_path):
    g = _graph()
    cfg = dict(population=8, generations=5, cache_dir=str(tmp_path),
               fit_surrogate=False)
    ga_search(g, _site_effect_fitness, GAConfig(seed=0, **cfg))
    _, ga2 = ga_search(g, _site_effect_fitness, GAConfig(seed=1, **cfg))
    assert ga2.surrogate_kind == "static"


# ---------------------------------------------------------------------------
# compile-parallel / time-serial phase
# ---------------------------------------------------------------------------


class _DeterministicTwoPhase:
    """prepare/measure fitness with exact, timing-free Evaluations."""

    def __init__(self, delay=0.0):
        import time
        self._sleep = (lambda: time.sleep(delay)) if delay else (lambda: None)
        self.prepared: list[tuple] = []

    def prepare(self, bits):
        self._sleep()                 # stands in for the warm-up compile
        self.prepared.append(tuple(bits))
        return ("prepared", tuple(bits))

    def measure(self, prep):
        tag, bits = prep
        assert tag == "prepared"
        return Evaluation(bits, 1.0 + 0.1 * sum(bits), True,
                          {"phase": "two"})

    def __call__(self, bits):
        return self.measure(self.prepare(bits))


def test_overlapped_equals_serial_fitness_values():
    pop = [(i % 2, (i // 2) % 2, (i // 4) % 2) for i in range(8)]
    serial = Evaluator(_DeterministicTwoPhase(),
                       compile_workers=0).evaluate_batch(pop)
    ev = Evaluator(_DeterministicTwoPhase(delay=0.01), compile_workers=4)
    overlapped = ev.evaluate_batch(pop)
    assert [(r.bits, r.time_s, r.valid, r.detail) for r in serial] \
        == [(r.bits, r.time_s, r.valid, r.detail) for r in overlapped]
    assert ev.stats.overlapped_compiles == 8
    assert ev.stats.compile_serial_s > 0
    assert ev.stats.compile_wall_s > 0
    assert "compile_overlap_saved_s" in ev.stats.as_dict()


def test_overlapped_ga_identical_to_serial_at_fixed_seed():
    cfg = dict(population=10, generations=5, seed=3)
    r_ser = run_ga(4, _DeterministicTwoPhase(),
                   GAConfig(**cfg, compile_workers=0))
    r_ovl = run_ga(4, _DeterministicTwoPhase(delay=0.002),
                   GAConfig(**cfg, compile_workers=4))
    assert r_ser.best.bits == r_ovl.best.bits
    assert r_ser.best.time_s == r_ovl.best.time_s
    assert [h["best_time_s"] for h in r_ser.history] \
        == [h["best_time_s"] for h in r_ovl.history]
    assert r_ser.evaluations == r_ovl.evaluations
    assert r_ovl.compile_overlap_saved_s >= 0.0


def test_overlap_prepare_failures_match_serial():
    class Flaky(_DeterministicTwoPhase):
        def prepare(self, bits):
            if sum(bits) == 2:        # deterministic "compile error"
                return ("prepared", tuple(bits))
            return super().prepare(bits)

        def measure(self, prep):
            tag, bits = prep
            if sum(bits) == 2:
                return Evaluation(bits, float("inf"), False,
                                  {"error": "boom"})
            return super().measure(prep)

    pop = [(0, 0), (1, 0), (1, 1), (0, 1)]
    serial = Evaluator(Flaky(), compile_workers=0).evaluate_batch(pop)
    overlapped = Evaluator(Flaky(), compile_workers=4).evaluate_batch(pop)
    assert [(r.bits, r.time_s, r.valid) for r in serial] \
        == [(r.bits, r.time_s, r.valid) for r in overlapped]
    bad = next(r for r in overlapped if r.bits == (1, 1))
    assert not bad.valid and bad.detail["error"] == "boom"


def test_wallclock_two_phase_matches_call_semantics():
    from repro.core.fitness import WallClockFitness

    calls = []

    def build(bits):
        calls.append(tuple(bits))
        if sum(bits) > 1:
            raise RuntimeError("no such kernel")
        return lambda: {"y": np.asarray([float(sum(bits))])}

    ref = {"y": np.asarray([0.0])}
    fit = WallClockFitness(build, reference_output=ref, repeats=1)
    # failure path: prepare carries the same Evaluation __call__ returns
    direct = fit((1, 1))
    phased = fit.measure(fit.prepare((1, 1)))
    assert (direct.bits, direct.valid, direct.detail) \
        == (phased.bits, phased.valid, phased.detail)
    # verification failure path
    direct = fit((1, 0))
    phased = fit.measure(fit.prepare((1, 0)))
    assert not direct.valid and not phased.valid
    assert "verify" in direct.detail and "verify" in phased.detail
    # success path: valid with a finite timing (values are wall-clock, so
    # only the structure is asserted)
    ok = fit.measure(fit.prepare((0, 0)))
    assert ok.valid and math.isfinite(ok.time_s)


def test_serial_only_wallclock_overlap_keeps_workers_serial():
    """compile_workers must not activate the thread-parallel *timing* path:
    only prepare overlaps, measure order is batch order."""
    order = []

    class Ordered(_DeterministicTwoPhase):
        def measure(self, prep):
            order.append(prep[1])
            return super().measure(prep)

    pop = [(1, 0), (0, 1), (1, 1), (0, 0)]
    Evaluator(Ordered(), compile_workers=4).evaluate_batch(pop)
    assert order == pop               # strictly serial, in batch order


# ---------------------------------------------------------------------------
# resolution fallbacks fold into the phenotype key
# ---------------------------------------------------------------------------


def test_phenotype_key_folds_resolver_fallbacks():
    from repro.core.genes import VARIANT_ALPHABET

    g = RegionGraph([
        Region("site", "loop", uses=frozenset({"a"}), defs=frozenset({"a"}),
               offloadable=True,
               alternatives=("ref", "fused_jnp", "pallas"), trip_count=4),
    ], "ir", "resolve")
    coding = coding_from_graph(g, destinations=VARIANT_ALPHABET)

    def resolver(region, impl):       # both variants fall back to ref
        return "ref" if str(impl) in ("fused_jnp", "pallas") else impl

    calls = []

    def fit(bits):
        calls.append(tuple(bits))
        return Evaluation(tuple(bits), 1.0, True)

    ev = Evaluator(fit, phenotype_key=phenotype_key(coding,
                                                    resolver=resolver))
    out = ev.evaluate_batch([(0,), (1,), (2,)])
    assert len(calls) == 1, "all three decode to the ref program"
    assert [r.bits for r in out] == [(0,), (1,), (2,)]
    # without the resolver the variants are distinct phenotypes
    calls2 = []

    def fit2(bits):
        calls2.append(tuple(bits))
        return Evaluation(tuple(bits), 1.0, True)

    Evaluator(fit2,
              phenotype_key=phenotype_key(coding)).evaluate_batch(
        [(0,), (1,), (2,)])
    assert len(calls2) == 3


def test_phenotype_key_resolver_errors_are_harmless():
    g = _graph(2)
    coding = coding_from_graph(g)

    def broken(region, impl):
        raise RuntimeError("resolver exploded")

    key = phenotype_key(coding, resolver=broken)
    assert key((0, 1)) == phenotype_key(coding)((0, 1))


def test_jaxpr_engine_resolved_impl_dedups_fallback_variants():
    """End to end on the real engine: a carry-only scan rejects both kernel
    variants, so gene values 1/2 resolve to ref and share one phenotype."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core import OffloadConfig
    from repro.core.frontends.registry import get_frontend

    def app(xs, w):
        def body(c, _):
            return jnp.tanh(c @ w), ()
        c, _ = jax.lax.scan(body, xs, None, length=3)
        return c

    xs = jnp.ones((8, 8), jnp.float32)
    w = jnp.ones((8, 8), jnp.float32) * 0.1
    fe = get_frontend("jaxpr")
    cfg = OffloadConfig(repeats=1, options={"example_args": (xs, w)})
    graph = fe.build_graph(app, None, cfg)
    bundle = fe.make_fitness(graph, app, None, cfg)
    assert bundle.impl_resolver is not None
    matched = [r.name for r in graph.offloadable()
               if r.meta.get("pattern")]
    for region in matched:
        chosen1 = bundle.impl_resolver(region, "fused_jnp")
        chosen2 = bundle.impl_resolver(region, "pallas")
        # whatever binds, resolution is deterministic and "ref" on fallback
        assert isinstance(chosen1, str) and isinstance(chosen2, str)
    # unmatched regions: any requested variant resolves to ref (substitute
    # leaves their equations untouched), so their genes are phenotype-inert
    unmatched = [r.name for r in graph.offloadable()
                 if not r.meta.get("pattern") and r.meta.get("eqn_span")]
    for region in unmatched:
        assert bundle.impl_resolver(region, "kernel") == "ref"
