"""Cross-frontend differential conformance suite.

The paper's claim is ONE offload method across source languages; PR 4's
claim is that *variant selection* is part of that method on every frontend.
This suite proves it differentially: the same logical workloads (attention,
rmsnorm, recurrence) planned via the python_ast, jaxpr, and module
frontends produce

  * numerically equivalent outputs per chosen variant (allclose against the
    frontend's reference AND against each other at matched tolerances),
  * a uniform :class:`~repro.core.variants.SubstitutionReport`
    (``OffloadResult.report``) of the same shape on every frontend, and
  * bit-identical serial vs parallel plans (reports included).

The report-shape contracts parametrize over ``frontend_names()`` so a
future frontend is auto-covered the moment it registers (it must then add a
workload fixture here — the test fails loudly until it does).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (GAConfig, OffloadConfig, Offloader, Evaluation,
                        Region, RegionGraph, SubstitutionReport,
                        VARIANT_ALPHABET, coding_from_graph, frontend_names,
                        get_frontend, plan_offload)

RTOL = ATOL = 2e-2           # matched tolerances: the verifier's own bars

# ---------------------------------------------------------------------------
# the shared logical workloads
# ---------------------------------------------------------------------------
#
# One rng seeds every frontend's inputs, so the python interpreter, the
# substituted jaxpr program, and the module executors all compute over the
# same numbers.

S, D = 12, 8                 # attention/recurrence extent (interp-friendly)
RS, RD = 48, 16              # rmsnorm rows/cols


def _rng():
    return np.random.default_rng(7)


# --- python_ast sources: the paper's numeric-Python form -------------------

ATTN_SRC = """
def attn_app(q, k, v, n, d, scale):
    out = np.zeros((n, d))
    for i in range(n):
        m = -1e30
        for j in range(i + 1):
            s = 0.0
            for t in range(d):
                s = s + q[i][t] * k[j][t]
            s = s * scale
            if s > m:
                m = s
        z = 0.0
        for j in range(i + 1):
            e = 0.0
            for t in range(d):
                e = e + q[i][t] * k[j][t]
            z = z + np.exp(e * scale - m)
        for t in range(d):
            acc = 0.0
            for j in range(i + 1):
                e = 0.0
                for u in range(d):
                    e = e + q[i][u] * k[j][u]
                acc = acc + np.exp(e * scale - m) / z * v[j][t]
            out[i][t] = acc
    return out
"""

RMS_SRC = """
def rms_app(x, scale, n, d):
    out = np.zeros((n, d))
    for i in range(n):
        ss = 0.0
        for t in range(d):
            ss = ss + x[i][t] * x[i][t]
        inv = 1.0 / np.sqrt(ss / d + 1e-06)
        for t in range(d):
            out[i][t] = x[i][t] * inv * (1.0 + scale[t])
    return out
"""

REC_SRC = """
def rec_app(a, b, h, n, d):
    out = np.zeros((n, d))
    for t in range(n):
        for c in range(d):
            h[c] = np.exp(a[t][c]) * h[c] + b[t][c]
            out[t][c] = h[c]
    return out
"""


def _attn_inputs():
    r = _rng()
    return dict(q=r.standard_normal((S, D)), k=r.standard_normal((S, D)),
                v=r.standard_normal((S, D)))


def _rms_inputs():
    r = _rng()
    return dict(x=r.standard_normal((RS, RD)),
                scale=r.standard_normal(RD) * 0.1)


def _rec_inputs():
    r = _rng()
    return dict(a=-np.abs(r.standard_normal((S, D))) * 0.2,
                b=r.standard_normal((S, D)) * 0.5,
                h=np.zeros((D,)))


PY_WORKLOADS = {
    "attention": (ATTN_SRC, {"n": S, "d": D, "scale": 1.0 / math.sqrt(D)},
                  _attn_inputs, "out", "softmax_attention"),
    "rmsnorm": (RMS_SRC, {"n": RS, "d": RD}, _rms_inputs, "out", "rmsnorm"),
    "recurrence": (REC_SRC, {"n": S, "d": D}, _rec_inputs, "out",
                   "linear_recurrence"),
}


# --- jaxpr apps: the same math, traced ------------------------------------


def _jx_attn_app(q, k, v):
    s = q @ k.T / jnp.sqrt(q.shape[-1] * 1.0)
    mask = jnp.tril(jnp.ones((q.shape[0], k.shape[0]), bool))
    return jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1) @ v


def _jx_rms_app(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1 + scale)


def _jx_rec_app(la, b):
    def step(h, ab):
        h = jnp.exp(ab[0]) * h + ab[1]
        return h, h
    _, hs = jax.lax.scan(step, jnp.zeros(la.shape[-1]), (la, b))
    return hs


def _jx_case(workload):
    if workload == "attention":
        i = _attn_inputs()
        return _jx_attn_app, tuple(jnp.asarray(i[n], jnp.float32)
                                   for n in ("q", "k", "v"))
    if workload == "rmsnorm":
        i = _rms_inputs()
        return _jx_rms_app, (jnp.asarray(i["x"], jnp.float32),
                             jnp.asarray(i["scale"], jnp.float32))
    i = _rec_inputs()
    return _jx_rec_app, (jnp.asarray(i["a"], jnp.float32),
                         jnp.asarray(i["b"], jnp.float32))


# ---------------------------------------------------------------------------
# per-frontend plan bundles (cached: make_fitness interprets/measures)
# ---------------------------------------------------------------------------

_PY_BUNDLES: dict = {}
_JX_BUNDLES: dict = {}


def _py_bundle(workload):
    if workload not in _PY_BUNDLES:
        src, consts, inputs_fn, out_name, pattern = PY_WORKLOADS[workload]
        inputs = inputs_fn()
        fe = get_frontend("python_ast")
        cfg = OffloadConfig(repeats=1, options={"consts": consts})
        program = fe.normalize_target(src, inputs, cfg)
        graph = fe.build_graph(program, inputs, cfg)
        bundle = fe.make_fitness(graph, program, inputs, cfg)
        coding = coding_from_graph(graph, exclude=bundle.claimed,
                                   destinations=bundle.destinations
                                   or ("cpu", "gpu"))
        from repro.core.frontends.ast_frontend import Executor
        env0 = Executor(program, {}, hoist_transfers=False).run(**inputs)
        _PY_BUNDLES[workload] = (fe, graph, bundle, coding, inputs,
                                 np.asarray(env0[out_name]))
    return _PY_BUNDLES[workload]


def _jx_bundle(workload):
    if workload not in _JX_BUNDLES:
        fn, args = _jx_case(workload)
        fe = get_frontend("jaxpr")
        cfg = OffloadConfig(repeats=1, options={"example_args": args})
        graph = fe.build_graph(fn, None, cfg)
        bundle = fe.make_fitness(graph, fn, None, cfg)
        coding = coding_from_graph(graph, exclude=bundle.claimed,
                                   destinations=bundle.destinations)
        _JX_BUNDLES[workload] = (fe, graph, bundle, coding, args,
                                 np.asarray(fn(*args)))
    return _JX_BUNDLES[workload]


def _values_for(coding, graph, pattern, gene_value):
    """All-reference chromosome with the matched site set to gene_value."""
    sites = [s.region for s in coding.sites
             if graph.by_name(s.region).meta.get("pattern") == pattern]
    assert sites, f"no gene site matched {pattern}"
    return tuple(gene_value if s.region == sites[0] else 0
                 for s in coding.sites), sites[0]


VARIANT_GENE = {"fused_jnp": 1, "pallas": 2}    # VARIANT_ALPHABET positions


# ---------------------------------------------------------------------------
# contract 1: per-variant numeric equivalence, python_ast vs jaxpr
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workload", sorted(PY_WORKLOADS))
@pytest.mark.parametrize("variant", sorted(VARIANT_GENE))
def test_python_and_jaxpr_variant_outputs_match(workload, variant):
    pattern = PY_WORKLOADS[workload][4]
    gene = VARIANT_GENE[variant]

    fe, graph, bundle, coding, inputs, py_ref = _py_bundle(workload)
    assert bundle.destinations == VARIANT_ALPHABET
    values, region = _values_for(coding, graph, pattern, gene)
    artifact = fe.apply_plan(graph, coding, values, bundle)
    assert artifact.report.substituted == {region: variant}, \
        artifact.report.fallbacks
    out_name = PY_WORKLOADS[workload][3]
    py_out = artifact.run(**inputs)[out_name]
    np.testing.assert_allclose(py_out, py_ref, rtol=RTOL, atol=ATOL)

    jfe, jgraph, jbundle, jcoding, args, jx_ref = _jx_bundle(workload)
    jvalues, jregion = _values_for(jcoding, jgraph, pattern, gene)
    sub = jfe.apply_plan(jgraph, jcoding, jvalues, jbundle)
    assert sub.report.substituted == {jregion: variant}, \
        sub.report.fallbacks
    jx_out = np.asarray(sub(*args))
    np.testing.assert_allclose(jx_out, jx_ref, rtol=RTOL, atol=ATOL)

    # the differential claim: two frontends, one workload, one variant,
    # numerically the same artifact output
    np.testing.assert_allclose(py_out, jx_out, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("workload", sorted(PY_WORKLOADS))
def test_report_shapes_identical_across_executable_frontends(workload):
    pattern = PY_WORKLOADS[workload][4]
    fe, graph, bundle, coding, _, _ = _py_bundle(workload)
    jfe, jgraph, jbundle, jcoding, _, _ = _jx_bundle(workload)
    values, region = _values_for(coding, graph, pattern, 1)
    jvalues, jregion = _values_for(jcoding, jgraph, pattern, 1)
    r1 = fe.apply_plan(graph, coding, values, bundle).report
    r2 = jfe.apply_plan(jgraph, jcoding, jvalues, jbundle).report
    for rep in (r1, r2):
        assert isinstance(rep, SubstitutionReport)
        assert set(rep.summary()) == {"substituted", "fallbacks"}
    c1 = next(c for c in r1.choices if c.region == region)
    c2 = next(c for c in r2.choices if c.region == jregion)
    # same fields, same pattern, same chosen variant — only region naming
    # is frontend-private
    assert (c1.pattern, c1.requested, c1.chosen) == \
        (c2.pattern, c2.requested, c2.chosen) == \
        (pattern, "fused_jnp", "fused_jnp")


def test_python_ast_roles_survive_swapped_operand_order():
    """Structural role inference: `k[j][t] * q[i][t]` (k textually first)
    must still bind (q, k, v) correctly — the ast analogue of the jaxpr
    span-order bug PR 3 fixed with dataflow role inference."""
    swapped = ATTN_SRC.replace("q[i][t] * k[j][t]", "k[j][t] * q[i][t]") \
                      .replace("q[i][u] * k[j][u]", "k[j][u] * q[i][u]")
    assert "k[j][t] * q[i][t]" in swapped
    inputs = _attn_inputs()
    fe = get_frontend("python_ast")
    cfg = OffloadConfig(repeats=1,
                        options={"consts": PY_WORKLOADS["attention"][1]})
    program = fe.normalize_target(swapped, inputs, cfg)
    graph = fe.build_graph(program, inputs, cfg)
    bundle = fe.make_fitness(graph, program, inputs, cfg)
    coding = coding_from_graph(graph, exclude=bundle.claimed,
                               destinations=bundle.destinations)
    from repro.core.frontends.ast_frontend import Executor
    ref = np.asarray(Executor(program, {}, hoist_transfers=False)
                     .run(**inputs)["out"])
    values, region = _values_for(coding, graph, "softmax_attention", 1)
    art = fe.apply_plan(graph, coding, values, bundle)
    assert art.report.substituted == {region: "fused_jnp"}
    np.testing.assert_allclose(art.run(**inputs)["out"], ref,
                               rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# contract 2: module-frontend variant knobs (ExecPlan.SITE_VARIANTS)
# ---------------------------------------------------------------------------


def test_module_gene_selects_extra_variant():
    from repro.core.pattern_db import PatternDB

    fe = get_frontend("module")
    # an empty DB: nothing is block-claimed, every knob stays in the gene
    cfg = OffloadConfig(db=PatternDB([]))
    graph = fe.build_graph(get_config("recurrentgemma_2b"), None, cfg)
    bundle = fe.make_fitness(graph, get_config("recurrentgemma_2b"), None,
                             cfg)
    assert bundle.destinations == VARIANT_ALPHABET
    coding = coding_from_graph(graph, exclude=bundle.claimed,
                               destinations=bundle.destinations)
    by_region = {s.region: i for i, s in enumerate(coding.sites)}
    assert "rglru_impl" in by_region, "recurrence knob must stay in the gene"
    for gene, expect in ((0, "step"), (1, "assoc"), (2, "chunked")):
        values = [0] * coding.length
        values[by_region["rglru_impl"]] = gene
        plan = fe.apply_plan(graph, coding, tuple(values), bundle)
        assert plan.rglru_impl == expect
    if "remat" in by_region:
        values = [0] * coding.length
        values[by_region["remat"]] = 2
        assert fe.apply_plan(graph, coding, tuple(values),
                             bundle).remat == "full"
    # a binary site clamps: gene 2 selects its (only) offload impl
    values = [0] * coding.length
    values[by_region["norm_impl"]] = 2
    assert fe.apply_plan(graph, coding, tuple(values),
                         bundle).norm_impl == "fused"


@pytest.mark.parametrize("impl", ["assoc", "chunked"])
def test_module_rglru_variants_numerically_equivalent(impl):
    from repro.models import rglru
    from repro.models.plan import ExecPlan

    r = _rng()
    log_a = jnp.asarray(-np.abs(r.standard_normal((2, S, D))) * 0.2,
                        jnp.float32)
    b = jnp.asarray(r.standard_normal((2, S, D)) * 0.5, jnp.float32)
    h0 = jnp.zeros((2, D), jnp.float32)
    ref_hs, ref_hT = rglru.rglru_scan(log_a, b, h0,
                                      ExecPlan(rglru_impl="step"))
    hs, hT = rglru.rglru_scan(log_a, b, h0, ExecPlan(rglru_impl=impl,
                                                     rglru_chunk=8))
    np.testing.assert_allclose(np.asarray(hs), np.asarray(ref_hs),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(ref_hT),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.slow
def test_module_planned_variants_match_reference_forward():
    """Full-model equivalence: a plan selecting the extra rg-LRU variant
    computes the same loss as the reference plan."""
    from repro.models import build_model
    from repro.models.plan import REFERENCE_PLAN

    cfg = get_config("recurrentgemma_2b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = model.demo_batch(jax.random.key(1), 2, 32)
    base = REFERENCE_PLAN.replace(compute_dtype="float32", rglru_chunk=16)
    ref, _ = model.loss(params, batch, base)
    for impl in ("assoc", "chunked"):
        out, _ = model.loss(params, batch, base.replace(rglru_impl=impl))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# contract 3: uniform report + serial==parallel on EVERY registered frontend
# ---------------------------------------------------------------------------
#
# These parametrize over frontend_names(), so registering a new frontend
# automatically extends the suite — it fails until a fixture is added here.

_IR_GRAPH_REGIONS = [
    Region("hot", "loop", uses=frozenset({"a"}), defs=frozenset({"a"}),
           offloadable=True, alternatives=("ref", "kernel"), trip_count=9),
    Region("mid", "loop", uses=frozenset({"b"}), defs=frozenset({"b"}),
           offloadable=True, alternatives=("ref", "kernel", "extra"),
           trip_count=4),
]


def _frontend_fixture(name):
    if name == "python_ast":
        src, consts, inputs_fn, _, _ = PY_WORKLOADS["rmsnorm"]
        return src, inputs_fn(), {"repeats": 1, "options": {"consts": consts}}
    if name == "jaxpr":
        fn, args = _jx_case("recurrence")
        return fn, None, {"options": {"example_args": args}}
    if name == "module":
        return get_config("recurrentgemma_2b"), None, {}
    if name == "ir":
        return RegionGraph([Region(r.name, r.kind, defs=r.defs, uses=r.uses,
                                   offloadable=r.offloadable,
                                   alternatives=r.alternatives,
                                   trip_count=r.trip_count)
                            for r in _IR_GRAPH_REGIONS], "ir", "diff-toy"), \
            None, {}
    raise AssertionError(
        f"frontend {name!r} is registered but has no differential-suite "
        f"fixture: add one to tests/test_frontend_differential.py")


def _det_fitness(values) -> Evaluation:
    t = 1.0 + 0.05 * sum(int(v) * (i + 1) for i, v in enumerate(values))
    return Evaluation(tuple(values), t, True)


def _plan(name, workers=0, seed=5):
    target, inputs, kwargs = _frontend_fixture(name)
    cfg = OffloadConfig(ga=GAConfig(population=6, generations=2, seed=seed,
                                    workers=workers),
                        fitness_fn=_det_fitness, **kwargs)
    return Offloader(cfg).plan(target, inputs)


@pytest.mark.parametrize("name", sorted(frontend_names()))
def test_every_frontend_reports_uniformly(name):
    res = _plan(name)
    rep = res.report
    assert isinstance(rep, SubstitutionReport)
    gene_sites = {s.region for s in res.coding.sites}
    regions = [c.region for c in rep.choices]
    assert len(regions) == len(set(regions)), "one choice per region"
    assert set(regions) >= gene_sites, "every gene site must be reported"
    for c in rep.choices:
        assert isinstance(c.requested, str) and isinstance(c.chosen, str)
        assert isinstance(c.why, str)
        assert c.pattern is None or isinstance(c.pattern, str)
    assert set(rep.summary()) == {"substituted", "fallbacks"}
    assert res.summary()["substituted"] == rep.substituted


@pytest.mark.parametrize("name", sorted(frontend_names()))
def test_every_frontend_serial_parallel_report_identical(name):
    r_ser = _plan(name, workers=0)
    r_par = _plan(name, workers=4)
    assert r_ser.best.bits == r_par.best.bits
    assert r_ser.report == r_par.report
    assert [h["best_time_s"] for h in r_ser.ga.history] == \
        [h["best_time_s"] for h in r_par.ga.history]


# ---------------------------------------------------------------------------
# contract 4: function-block genes — one attention-stack workload, every
# frontend (auto-extends: a new frontend must add a fixture or declare
# itself block-free below)
# ---------------------------------------------------------------------------

BS, BD = 16, 8               # block workload extent (interp-friendly)

BLOCK_SRC = """
def attn_stack(x, scale, wq, wk, wv):
    S = x.shape[0]
    D = x.shape[1]
    xn = np.zeros_like(x)
    q = np.zeros_like(x)
    k = np.zeros_like(x)
    v = np.zeros_like(x)
    out = np.zeros_like(x)
    for i in range(S):
        ss = 0.0
        for j in range(D):
            ss += x[i, j] * x[i, j]
        r = 1.0 / math.sqrt(ss / D + 1e-06)
        for j in range(D):
            xn[i, j] = x[i, j] * r * (1.0 + scale[j])
    for i in range(S):
        for j in range(D):
            sq = 0.0
            sk = 0.0
            sv = 0.0
            for t in range(D):
                sq += xn[i, t] * wq[t, j]
                sk += xn[i, t] * wk[t, j]
                sv += xn[i, t] * wv[t, j]
            q[i, j] = sq
            k[i, j] = sk
            v[i, j] = sv
    for i in range(S):
        m = -1e30
        for j in range(i + 1):
            s = 0.0
            for t in range(D):
                s += q[i, t] * k[j, t]
            s = s / math.sqrt(D)
            if s > m:
                m = s
        z = 0.0
        for j in range(i + 1):
            s = 0.0
            for t in range(D):
                s += q[i, t] * k[j, t]
            w = math.exp(s / math.sqrt(D) - m)
            z += w
            for t in range(D):
                out[i, t] += w * v[j, t]
        for t in range(D):
            out[i, t] = out[i, t] / z
    return out
"""


def _block_inputs():
    r = _rng()
    return dict(x=r.standard_normal((BS, BD)),
                scale=r.standard_normal(BD) * 0.1,
                wq=r.standard_normal((BD, BD)) / math.sqrt(BD),
                wk=r.standard_normal((BD, BD)) / math.sqrt(BD),
                wv=r.standard_normal((BD, BD)) / math.sqrt(BD))


def _jx_block_case():
    @jax.jit
    def attention(q, k, v):
        s = q @ k.T / jnp.sqrt(q.shape[-1] * 1.0)
        mask = jnp.tril(jnp.ones((q.shape[0], q.shape[0]), bool))
        return jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1) @ v

    def model(x, scale, wq, wk, wv):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        xn = x * jax.lax.rsqrt(var + 1e-6) * (1.0 + scale)
        return attention(xn @ wq, xn @ wk, xn @ wv)

    i = _block_inputs()
    return model, tuple(jnp.asarray(i[n], jnp.float32)
                        for n in ("x", "scale", "wq", "wk", "wv"))


_BLOCK_BUNDLES: dict = {}

#: frontends whose planning pipeline has no function-block pass — they must
#: still plan the absence uniformly (no member-carrying gene sites)
_BLOCK_FREE = {"module", "ir"}


def _block_bundle(name):
    if name in _BLOCK_BUNDLES:
        return _BLOCK_BUNDLES[name]
    if name == "python_ast":
        inputs = _block_inputs()
        fe = get_frontend("python_ast")
        cfg = OffloadConfig(repeats=1)
        program = fe.normalize_target(BLOCK_SRC, inputs, cfg)
        graph = fe.build_graph(program, inputs, cfg)
        bundle = fe.make_fitness(graph, program, inputs, cfg)
        from repro.core.frontends.ast_frontend import Executor
        ref = np.asarray(Executor(program, {}, hoist_transfers=False)
                         .run(**inputs)["out"])
        runner = lambda art: np.asarray(art.run(**inputs)["out"])  # noqa: E731
        target = program
    elif name == "jaxpr":
        fn, args = _jx_block_case()
        fe = get_frontend("jaxpr")
        cfg = OffloadConfig(repeats=1, options={"example_args": args})
        graph = fe.build_graph(fn, None, cfg)
        bundle = fe.make_fitness(graph, fn, None, cfg)
        ref = np.asarray(fn(*args))
        runner = lambda sub: np.asarray(sub(*args))  # noqa: E731
        target = fn
    else:
        raise AssertionError(
            f"frontend {name!r} is registered but has no function-block "
            f"fixture: add one (or list it in _BLOCK_FREE) in "
            f"tests/test_frontend_differential.py")
    coding = coding_from_graph(graph, exclude=bundle.claimed,
                               destinations=bundle.destinations)
    _BLOCK_BUNDLES[name] = (fe, graph, bundle, coding, ref, runner, target)
    return _BLOCK_BUNDLES[name]


def _block_values(coding, graph, gene):
    blocks = [r for r in graph.regions if r.meta.get("block_members")]
    assert blocks, "attention stack must yield a function-block region"
    fb = blocks[0]
    values = tuple(gene if s.region == fb.name else 0 for s in coding.sites)
    return values, fb


@pytest.mark.parametrize("name", sorted(frontend_names()))
def test_block_genes_uniform_across_frontends(name):
    if name in _BLOCK_FREE:
        res = _plan(name)
        assert all(not s.members for s in res.coding.sites)
        return
    fe, graph, bundle, coding, ref, runner, target = _block_bundle(name)
    values, fb = _block_values(coding, graph, 1)
    site = next(s for s in coding.sites if s.region == fb.name)
    assert site.members == tuple(fb.meta["block_members"])
    assert len(site.members) >= 2, "a block spans several regions"
    # an active block gene claims its members on every frontend
    claimed = coding.claimed_members(values)
    assert claimed == frozenset(site.members)
    decoded = coding.decode(values)
    assert decoded[fb.name] != site.ref_impl
    for m in site.members:
        if m in decoded:
            assert decoded[m] == \
                next(s for s in coding.sites if s.region == m).ref_impl


@pytest.mark.parametrize("gene", [1, 2])
def test_block_variant_outputs_match_python_vs_jaxpr(gene):
    outs = {}
    for name in ("python_ast", "jaxpr"):
        fe, graph, bundle, coding, ref, runner, target = _block_bundle(name)
        values, fb = _block_values(coding, graph, gene)
        impl = coding.decode(values)[fb.name]
        artifact = fe.apply_plan(graph, coding, values, bundle)
        assert artifact.report.substituted.get(fb.name) == impl, \
            artifact.report.fallbacks
        out = runner(artifact)
        np.testing.assert_allclose(out, ref, rtol=RTOL, atol=ATOL)
        outs[name] = (impl, out)
    # the differential claim, now at block granularity: both frontends
    # bound the same block implementation and computed the same numbers
    assert outs["python_ast"][0] == outs["jaxpr"][0]
    np.testing.assert_allclose(outs["python_ast"][1], outs["jaxpr"][1],
                               rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# contract 5: measured GA on the python_ast frontend picks a real variant
# ---------------------------------------------------------------------------


def test_python_ast_ga_selects_measured_variant():
    """The PR's acceptance bar: under measured wall-clock fitness the GA
    assigns a non-cpu variant destination (gpu_fused / gpu_pallas) to the
    matched site, the artifact verifies, and the report names the variant."""
    src, consts, inputs_fn, _, pattern = PY_WORKLOADS["rmsnorm"]
    res = plan_offload(src, inputs_fn(), config=OffloadConfig(
        ga=GAConfig(population=6, generations=2, seed=0), repeats=1,
        options={"consts": consts}))
    assert res.frontend == "python_ast"
    assert res.coding.destinations == VARIANT_ALPHABET
    assert any(d in ("gpu_fused", "gpu_pallas")
               for d in res.destinations.values()), res.destinations
    assert res.verification["verified"]
    assert any(c.chosen in ("fused_jnp", "pallas") and c.pattern == pattern
               for c in res.report.choices), res.report.choices
    assert res.artifact.report is res.report
    # and the interpreted path really was slower: measured speedup > 1
    assert res.speedup > 1.0


def test_python_ast_proposes_no_mesh_genes_on_a_multi_device_host(
        monkeypatch):
    """The interpreter never runs a region sharded, so even where the host
    reports eight devices the python_ast frontend proposes the variant
    alphabet alone: no mesh gene it could not execute."""
    from repro.core import genes

    monkeypatch.setattr(genes, "_PROBED_DEVICE_COUNT", 8)
    src, consts, inputs_fn, _, _ = PY_WORKLOADS["rmsnorm"]
    ctx = Offloader(OffloadConfig(repeats=1, options={"consts": consts})
                    ).prepare(src, inputs_fn())
    assert ctx.frontend == "python_ast"
    assert ctx.bundle.destinations == VARIANT_ALPHABET
    assert ctx.coding.destinations == VARIANT_ALPHABET


@pytest.mark.parametrize("name", ["python_ast", "jaxpr"])
def test_ga_selects_block_gene_and_members_stay_ref(name):
    """Through the whole pipeline: under a fitness that rewards the block
    gene the GA selects it, its member regions end on their reference path
    in the final pattern, and the report names the block's implementation."""
    if name == "python_ast":
        target, inputs, options = BLOCK_SRC, _block_inputs(), {}
    else:
        target, args = _jx_block_case()
        inputs, options = None, {"example_args": args}
    off = Offloader(OffloadConfig(
        repeats=1, ga=GAConfig(population=8, generations=3, seed=0),
        options=options))
    ctx = off.prepare(target, inputs)
    block = next(s for s in ctx.coding.sites if s.members)

    def fitness(values) -> Evaluation:
        decoded = ctx.coding.decode(values)
        t = 1.0 - 0.05 * sum(decoded[s.region] != s.ref_impl
                             for s in ctx.coding.sites)
        if decoded[block.region] != block.ref_impl:
            t -= 0.5
        return Evaluation(tuple(values), t, True)

    ctx.config.fitness_fn = fitness
    res = off.search(ctx)
    assert res.frontend == name
    assert res.pattern[block.region] != block.ref_impl
    refs = {s.region: s.ref_impl for s in res.coding.sites}
    for member in block.members:
        if member in refs:
            assert res.pattern[member] == refs[member]
    assert res.report.substituted.get(block.region) == \
        res.pattern[block.region]
