"""Kernel substitution engine: per-variant numeric equivalence, predicate
fallbacks, and the measured jaxpr plan -> substitute -> verify loop."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GAConfig, OffloadConfig, Offloader, SubstitutedCallable,
                        SubstitutionEngine, VARIANT_ALPHABET, plan_offload)
from repro.core.frontends import jaxpr_frontend as jf
from repro.core.pattern_db import default_db
from repro.core.verifier import verify
from repro.kernels.registry import (CallSite, VariantUnavailable,
                                    auto_variant_order, default_registry)

EXECUTABLE_VARIANTS = ("fused_jnp", "pallas")


def _arr(rng, *shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
# pattern apps: traced programs containing one matchable region each
# ---------------------------------------------------------------------------


def _attention_app(q, k, v, w):
    s = q @ k.T / jnp.sqrt(q.shape[-1] * 1.0)
    mask = jnp.tril(jnp.ones((q.shape[0], k.shape[0]), bool))
    h = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1) @ v
    def body(c, _):
        return jnp.tanh(c @ w), ()
    h, _ = jax.lax.scan(body, h, None, length=2)
    return h


def _recurrence_app(la, b):
    def step(h, ab):
        h = jnp.exp(ab[0]) * h + ab[1]
        return h, h
    _, hs = jax.lax.scan(step, jnp.zeros(la.shape[-1]), (la, b))
    return hs * 1.5


def _wkv_app(r, k, v, lw, u):
    def step(s, rkvw):
        rt, kt, vt, lwt = rkvw
        kv = kt[:, None] * vt[None, :]
        y = rt @ (s + u[:, None] * kv)
        return jnp.exp(lwt)[:, None] * s + kv, y
    _, ys = jax.lax.scan(step, jnp.zeros((r.shape[-1], v.shape[-1])),
                         (r, k, v, lw))
    return ys


@jax.jit
def _rmsnorm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1 + scale)


def _rmsnorm_app(x, scale, w):
    return _rmsnorm(x, scale) @ w


def _attention_case(rng, s, d, dtype=jnp.float32):
    # q, k, v must be DISTINCT: aliased operands (q, q, q) make an operand-
    # order bug in span binding numerically invisible
    q = _arr(rng, s, d, dtype=dtype)
    k = _arr(rng, s, d, dtype=dtype)
    v = _arr(rng, s, d, dtype=dtype)
    w = _arr(rng, d, d, dtype=dtype, scale=0.1)
    return _attention_app, (q, k, v, w), "softmax_attention"


def _recurrence_case(rng, s, d, dtype=jnp.float32):
    la = -jnp.abs(_arr(rng, s, d, dtype=dtype)) * 0.2
    b = _arr(rng, s, d, dtype=dtype, scale=0.5)
    return _recurrence_app, (la, b), "linear_recurrence"


def _wkv_case(rng, s, d, dtype=jnp.float32):
    r = _arr(rng, s, d, dtype=dtype, scale=0.5)
    k = _arr(rng, s, d, dtype=dtype, scale=0.5)
    v = _arr(rng, s, d, dtype=dtype, scale=0.5)
    lw = -jnp.abs(_arr(rng, s, d, dtype=dtype)) * 0.3
    u = _arr(rng, d, dtype=dtype, scale=0.1)
    return _wkv_app, (r, k, v, lw, u), "wkv_recurrence"


def _rmsnorm_case(rng, s, d, dtype=jnp.float32):
    x = _arr(rng, s, d, dtype=dtype)
    sc = _arr(rng, d, dtype=dtype, scale=0.1)
    w = _arr(rng, d, d, dtype=dtype)
    return _rmsnorm_app, (x, sc, w), "rmsnorm"


CASES = {
    "softmax_attention": _attention_case,
    "linear_recurrence": _recurrence_case,
    "wkv_recurrence": _wkv_case,
    "rmsnorm": _rmsnorm_case,
}


def _engine_for(fn, args):
    graph = jf.build_graph(fn, *args)
    jf.annotate_variants(graph, default_db())
    return SubstitutionEngine(fn, args, graph)


def _matched_region(engine, pattern):
    regions = [r.name for r in engine.graph.offloadable()
               if r.meta.get("pattern") == pattern]
    assert regions, f"no region matched {pattern}"
    return regions[0]


# ---------------------------------------------------------------------------
# per-variant numeric equivalence: every registry entry, >= 2 shapes/dtypes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pattern", sorted(CASES))
@pytest.mark.parametrize("variant", EXECUTABLE_VARIANTS)
@pytest.mark.parametrize("s,d,dtype", [
    (24, 8, jnp.float32),
    (33, 16, jnp.float32),       # ragged length exercises kernel padding
    (16, 8, jnp.bfloat16),
])
def test_variant_numeric_equivalence(rng, pattern, variant, s, d, dtype):
    fn, args, pat = CASES[pattern](rng, s, d, dtype=dtype)
    engine = _engine_for(fn, args)
    region = _matched_region(engine, pat)
    sub = engine.substitute({region: variant})
    assert sub.report.substituted == {region: variant}, \
        sub.report.fallbacks
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-2
    res = verify(engine.reference(), sub(*args), rtol=tol, atol=tol)
    assert res.ok, (pattern, variant, res)


def test_registry_covers_all_patterns():
    reg = default_registry()
    for pattern in CASES:
        assert set(reg.variant_names(pattern)) == set(EXECUTABLE_VARIANTS)
    assert set(auto_variant_order("tpu")) == set(EXECUTABLE_VARIANTS)
    assert auto_variant_order("cpu")[0] == "fused_jnp"
    assert auto_variant_order("tpu")[0] == "pallas"


# ---------------------------------------------------------------------------
# predicate rejection -> reference fallback, recorded and correct
# ---------------------------------------------------------------------------


def test_predicate_rejection_falls_back_to_ref(rng):
    # k/v shapes disagree with what the attention adapters accept (v has a
    # different head dim), so every variant must refuse and the engine must
    # run the original equations — bit-identically
    def odd_attention(q, k, v):
        s = q @ k.T / jnp.sqrt(q.shape[-1] * 1.0)
        mask = jnp.tril(jnp.ones((q.shape[0], k.shape[0]), bool))
        return jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1) @ v

    q = _arr(rng, 16, 8)
    v = _arr(rng, 16, 4)                      # head-dim mismatch vs q/k
    engine = _engine_for(odd_attention, (q, q, v))
    region = _matched_region(engine, "softmax_attention")
    for variant in EXECUTABLE_VARIANTS:
        sub = engine.substitute({region: variant})
        assert sub.report.substituted == {}
        assert region in sub.report.fallbacks
        assert variant in sub.report.fallbacks[region]
        # jit-vs-eager numerics differ only in fusion rounding
        np.testing.assert_allclose(
            np.asarray(sub(q, q, v)), np.asarray(odd_attention(q, q, v)),
            rtol=1e-5, atol=1e-5)


def test_scan_structure_rejection(rng):
    # a reverse scan must not bind the recurrence kernels
    def rev_rec(la, b):
        def step(h, ab):
            return jnp.exp(ab[0]) * h + ab[1], h
        _, hs = jax.lax.scan(step, jnp.zeros(la.shape[-1]), (la, b),
                             reverse=True)
        return hs

    la = _arr(rng, 12, 4)
    engine = _engine_for(rev_rec, (la, la))
    for r in engine.graph.offloadable():
        if r.meta.get("pattern") == "linear_recurrence":
            sub = engine.substitute({r.name: "pallas"})
            assert sub.report.substituted == {}
            np.testing.assert_allclose(
                np.asarray(sub(la, la)), np.asarray(rev_rec(la, la)),
                rtol=1e-5, atol=1e-5)


def test_carry_only_scan_rejects_instead_of_crashing(rng):
    # ys=None scan: one output, not (carry, ys) — the recurrence predicates
    # must refuse (VariantUnavailable -> ref fallback), not IndexError
    def carry_only(la, b):
        def step(h, ab):
            return jnp.exp(ab[0]) * h + ab[1], None
        h, _ = jax.lax.scan(step, jnp.zeros(la.shape[-1]), (la, b))
        return h

    la = _arr(rng, 12, 4)
    engine = _engine_for(carry_only, (la, la))
    for r in engine.graph.offloadable():
        sub = engine.substitute({r.name: "pallas"})
        assert sub.report.substituted == {}
        np.testing.assert_allclose(
            np.asarray(sub(la, la)), np.asarray(carry_only(la, la)),
            rtol=1e-5, atol=1e-5)


def test_unknown_impl_and_unmatched_region_fall_back(rng):
    fn, args, pat = _recurrence_case(rng, 12, 4)
    engine = _engine_for(fn, args)
    region = _matched_region(engine, pat)
    sub = engine.substitute({region: "no-such-variant"})
    assert sub.report.substituted == {}
    assert "unknown implementation" in sub.report.fallbacks[region]
    # "kernel" (legacy auto) resolves to the backend-preferred variant
    sub2 = engine.substitute({region: "kernel"})
    assert sub2.report.substituted == {region: auto_variant_order(
        jax.default_backend())[0]}


def test_substituted_callable_is_reusable_and_jitted(rng):
    fn, args, pat = _rmsnorm_case(rng, 16, 8)
    engine = _engine_for(fn, args)
    region = _matched_region(engine, pat)
    sub = engine.substitute({region: "fused_jnp"})
    assert isinstance(sub, SubstitutedCallable)
    first = np.asarray(sub(*args))
    second = np.asarray(sub(*args))          # cached jit path
    np.testing.assert_array_equal(first, second)
    assert "fused_jnp" in repr(sub)


# ---------------------------------------------------------------------------
# the measured jaxpr pipeline end to end (the PR's acceptance loop)
# ---------------------------------------------------------------------------


def test_jaxpr_plan_measures_substituted_callable(rng):
    fn, args, _ = _attention_case(rng, 32, 16)
    cfg = OffloadConfig(ga=GAConfig(population=6, generations=2, seed=0),
                        options={"example_args": args}, repeats=1)
    res = Offloader(cfg).plan(fn)

    # gene alphabet: the frontend proposed the variant alphabet
    assert res.coding.destinations == VARIANT_ALPHABET
    # speedup comes from wall-clock measurement, not the static stub
    assert res.verification["mode"] == "measured"
    assert res.verification["verified"]
    assert "static_cost" not in res.best.detail
    assert math.isfinite(res.baseline.time_s) and res.baseline.time_s > 0
    assert math.isfinite(res.speedup)
    # the artifact is a runnable substituted callable whose outputs verify
    # against the unsubstituted reference
    assert isinstance(res.artifact, SubstitutedCallable)
    v = verify(fn(*args), res.artifact(*args))
    assert v.ok, v
    # every accelerated gene decodes to a registry variant at its site
    decoded = res.coding.decode(res.best.bits)
    for region, impl in decoded.items():
        assert res.pattern[region] == impl


def test_jaxpr_plan_forced_substitution_verifies(rng):
    # pin the fitness so the search is deterministic, then check that the
    # engine the bundle carries substitutes the matched attention block
    fn, args, _ = _attention_case(rng, 32, 16)
    cfg = OffloadConfig(ga=GAConfig(population=6, generations=2, seed=0),
                        options={"example_args": args}, repeats=1)
    res = Offloader(cfg).plan(fn)
    engine = res.details["engine"]
    region = _matched_region(engine, "softmax_attention")
    for variant in EXECUTABLE_VARIANTS:
        v = engine.verify({region: variant})
        assert v.ok, (variant, v)


def test_jaxpr_static_cost_path_is_opt_in(rng):
    fn, args, _ = _attention_case(rng, 16, 8)
    res = plan_offload(fn, config=OffloadConfig(
        ga=GAConfig(population=6, generations=2, seed=0),
        options={"example_args": args, "static_cost": True}))
    assert res.verification["mode"] == "static-cost"
    assert not res.verification["verified"]
    assert res.best.detail.get("static_cost")
    assert isinstance(res.artifact, dict)    # impl map, not a callable


# ---------------------------------------------------------------------------
# function-block substitution: whole-span equivalence, claiming, fallbacks
# ---------------------------------------------------------------------------

BLOCK_VARIANTS = ("block_chunked", "block_fused")


def _attention_stack_case(rng, s=64, d=16):
    @jax.jit
    def attention(q, k, v):
        sc = q @ k.T / jnp.sqrt(q.shape[-1] * 1.0)
        mask = jnp.tril(jnp.ones((q.shape[0], q.shape[0]), bool))
        return jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1) @ v

    def model(x, scale, wq, wk, wv, wo):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        xn = x * jax.lax.rsqrt(var + 1e-6) * (1.0 + scale)
        q = xn @ wq
        k = xn @ wk
        v = xn @ wv
        o = attention(q, k, v)
        return x + o @ wo

    x = _arr(rng, s, d)
    scale = _arr(rng, d, scale=0.1)
    wq, wk, wv, wo = (_arr(rng, d, d, scale=1.0 / math.sqrt(d))
                      for _ in range(4))
    return model, (x, scale, wq, wk, wv, wo)


def _block_engine(rng):
    model, args = _attention_stack_case(rng)
    graph = jf.build_graph(model, *args)
    jf.annotate_variants(graph, default_db())
    jf.annotate_block_sites(graph, default_db())
    engine = SubstitutionEngine(model, args, graph)
    blocks = [r for r in graph.regions if r.meta.get("block_members")]
    assert blocks, "attention stack must produce a function-block region"
    return engine, blocks[0], model, args


@pytest.mark.parametrize("variant", BLOCK_VARIANTS)
def test_block_substitution_equivalence(rng, variant):
    engine, fb, model, args = _block_engine(rng)
    assert set(fb.alternatives) >= {"ref"} | set(BLOCK_VARIANTS)

    # block-granularity verification: adapter vs reference over the span
    res, chosen = engine.verify_block(fb.name, variant)
    assert chosen == variant
    assert res.ok, (variant, res)

    # whole-program substitution: the block adapter re-emits the span and
    # the claimed members drop to their reference path, reported as such
    sub = engine.substitute({fb.name: variant})
    assert sub.report.substituted[fb.name] == variant
    by_region = {c.region: c for c in sub.report.choices}
    for member in fb.meta["block_members"]:
        assert by_region[member].chosen == "ref"
        assert f"claimed by block {fb.name}" in by_region[member].why
    v = verify(model(*args), sub(*args))
    assert v.ok, (variant, v)


def test_block_gene_overrides_member_requests(rng):
    # a chromosome that turns on the block AND a claimed member: the block
    # wins, the member's request is overridden to ref (one owner per span)
    engine, fb, model, args = _block_engine(rng)
    member = fb.meta["block_members"][-1]
    sub = engine.substitute({fb.name: "block_fused", member: "fused_jnp"})
    assert sub.report.substituted == {fb.name: "block_fused"}
    choice = next(c for c in sub.report.choices if c.region == member)
    assert choice.chosen == "ref"
    assert f"claimed by block {fb.name}" in choice.why
    v = verify(model(*args), sub(*args))
    assert v.ok, v


def test_block_unknown_impl_releases_members(rng):
    # the block falls back to ref -> the members stay their own regions
    # (loop-level substitution still possible on them)
    engine, fb, model, args = _block_engine(rng)
    sub = engine.substitute({fb.name: "no-such-variant"})
    assert fb.name not in sub.report.substituted
    assert "unknown implementation" in sub.report.fallbacks[fb.name]
    for c in sub.report.choices:
        assert "claimed by block" not in c.why
    np.testing.assert_allclose(np.asarray(sub(*args)),
                               np.asarray(model(*args)),
                               rtol=1e-5, atol=1e-5)
    # verify_block on the same request is trivially the reference path
    res, chosen = engine.verify_block(fb.name, "no-such-variant")
    assert chosen == "ref" and res.ok


def test_block_predicate_rejection_falls_back_to_ref():
    # head dim beyond the kernel range: every attention_stack variant must
    # refuse via its predicate, and the shared fallback rule yields ref
    from repro.core.variants import resolve_variant

    d = 600                              # > the binder's 512 head-dim cap
    f32 = jnp.float32
    av = lambda *shape: jax.ShapeDtypeStruct(shape, f32)   # noqa: E731
    site = CallSite(pattern="attention_stack", kind="block",
                    in_avals=(av(32, d), av(d), av(d, d), av(d, d),
                              av(d, d)),
                    out_avals=(av(32, d),), out_used=(True,))
    for variant in BLOCK_VARIANTS:
        adapter, chosen, why = resolve_variant(site, variant)
        assert adapter is None and chosen == "ref"
        assert "head dim outside kernel range" in why


def test_block_sites_opt_out_leaves_graph_loop_only(rng):
    model, args = _attention_stack_case(rng)
    cfg = OffloadConfig(ga=GAConfig(population=6, generations=2, seed=0),
                        options={"example_args": args,
                                 "block_sites": False}, repeats=1)
    fe_res = Offloader(cfg).plan(model)
    assert not any(r.meta.get("block_members")
                   for r in fe_res.graph.regions)


def test_invalid_variant_result_is_rejected_by_verifier(rng):
    # non-causal attention *name*-matched to the causal kernels: the
    # substitution binds, but the output diverges -> the verifier rejects it
    # and the fitness marks the chromosome invalid (the paper's PCAST flow)
    @jax.jit
    def attention(q, k, v):                  # name match: "attention"
        s = q @ k.T / jnp.sqrt(q.shape[-1] * 1.0)
        return jax.nn.softmax(s, axis=-1) @ v    # NOT causal

    def noncausal_app(q, k, v, w):
        return jnp.tanh(attention(q, k, v) @ w)

    q = _arr(rng, 32, 16)
    w = _arr(rng, 16, 16, scale=0.1)
    graph = jf.build_graph(noncausal_app, q, q, q, w)
    jf.annotate_variants(graph, default_db())
    matched = [r.name for r in graph.offloadable()
               if r.meta.get("pattern") == "softmax_attention"]
    assert matched, "named call must name-match the attention pattern"
    engine = SubstitutionEngine(noncausal_app, (q, q, q, w), graph)
    v = engine.verify({matched[0]: "fused_jnp"})
    assert not v.ok                       # causal kernel != non-causal block

    # and through the pipeline: the same chromosome is measured invalid,
    # so the GA's winner keeps a verified pattern
    cfg = OffloadConfig(ga=GAConfig(population=6, generations=2, seed=0),
                        options={"example_args": (q, q, q, w)}, repeats=1)
    res = Offloader(cfg).plan(noncausal_app)
    assert res.verification["verified"]
    assert res.pattern[matched[0]] == "ref"


# ---------------------------------------------------------------------------
# mesh destinations: genuine sharded execution and cost-only fallback
# ---------------------------------------------------------------------------


def test_mesh_destination_executes_span_under_shard_map(rng):
    # n=1 is a degenerate but *genuine* mesh: available on any host, so the
    # full route — gene name -> _mesh_adapter -> sharded span -> numerics —
    # runs in-process on single-device CI
    fn, args, pat = _rmsnorm_case(rng, 16, 8)
    engine = _engine_for(fn, args)
    region = _matched_region(engine, pat)
    sub = engine.substitute({region: "ref"},
                            destinations={region: "mesh:data:1:batch"})
    choice = next(c for c in sub.report.choices if c.region == region)
    assert choice.requested == "mesh:data:1:batch"
    assert choice.chosen == "mesh:data:1:batch"
    assert "sharded over" in choice.why
    v = verify(fn(*args), sub(*args))
    assert v.ok, v


def test_mesh_unavailable_falls_back_to_variant_with_reason(rng):
    # a stored plan from a larger host, on a single-device host: the site
    # takes the normal variant path and the report says why
    fn, args, pat = _rmsnorm_case(rng, 16, 8)
    engine = _engine_for(fn, args)
    region = _matched_region(engine, pat)
    sub = engine.substitute({region: "fused_jnp"},
                            destinations={region: "mesh:data:8:batch"})
    choice = next(c for c in sub.report.choices if c.region == region)
    assert choice.requested == "mesh:data:8:batch"
    assert "unavailable" in choice.why and "1 device(s) < 8" in choice.why
    assert choice.chosen == "fused_jnp"
    v = verify(fn(*args), sub(*args))
    assert v.ok, v


def test_mesh_shape_rejection_falls_back_with_reason(rng):
    # batch extent 15 does not divide n=1? it does — use an indivisible
    # mesh instead: extent 15 on a 1-device mesh is fine, so force the
    # reject through a scalar-output span (no sharded dimension)
    def scalar_app(x, w):
        return jnp.sum(jnp.tanh(x @ w) ** 2) + jnp.sum(x * x) * 0.5

    x = _arr(rng, 8, 8)
    w = _arr(rng, 8, 8, scale=0.1)
    graph = jf.build_graph(scalar_app, x, w)
    jf.annotate_variants(graph, default_db())
    regions = [r.name for r in graph.offloadable()]
    assert regions
    engine = SubstitutionEngine(scalar_app, (x, w), graph)
    sub = engine.substitute({regions[0]: "ref"},
                            destinations={regions[0]: "mesh:data:1:batch"})
    choice = next(c for c in sub.report.choices if c.region == regions[0])
    assert "rejected" in choice.why
    assert choice.chosen == "ref"
    v = verify(scalar_app(x, w), sub(x, w))
    assert v.ok, v
