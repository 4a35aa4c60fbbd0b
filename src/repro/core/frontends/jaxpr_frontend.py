"""jaxpr frontend: traced-JAX callables -> Region IR -> substituted programs.

The "compiled language" path (the paper's C/Clang analogue): a JAX program
is traced to a ClosedJaxpr; control-flow equations (scan / while / cond /
user jit closed calls) become *loop/block* regions with their own
characteristic vectors, contiguous simple equations become *stmt* regions.
Small glue calls (a jitted ``tril`` or ``where`` with a handful of inner
equations) are folded into the surrounding run so a hand-written attention
stays one matchable block instead of fragmenting at every jnp helper.
Variable def/use sets come from the equation in/out vars, callees from
primitive names plus closed-call names — which is what the pattern DB's
name matching keys on (e.g. a user function named ``flash_attention``
matches directly, the paper's library-name match).

Every region records its equation span (``meta["eqn_span"]``), and matched
regions are annotated with their pattern and the kernel registry's variant
alphabet (:func:`annotate_variants`) — which is what lets the substitution
engine (:mod:`repro.core.substitution`) turn a plan into a *runnable*
program and :meth:`JaxprFrontend.make_fitness` measure real wall-clock
instead of the static transfer-cost stub.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.core import similarity as sim
from repro.core.ir import Region, RegionGraph

_LOOP_PRIMS = {"scan", "while", "fori_loop", "cond", "jit", "custom_vjp_call",
               "custom_jvp_call", "remat", "checkpoint", "closed_call", "core_call"}

#: control-flow primitives are never glue, whatever their body size
_CONTROL_PRIMS = {"scan", "while", "fori_loop", "cond"}

#: a closed call with fewer inner equations than this is jnp-internal glue
#: (tril, where, ...) and folds into the surrounding statement run — the
#: same ">= 5 equations is a functional structure" rule the flush uses.
_GLUE_MAX_EQNS = 4


def _eqn_callees(eqn) -> tuple:
    names = [eqn.primitive.name]
    for k in ("name", "fun_name"):
        v = eqn.params.get(k)
        if isinstance(v, str):
            names.append(v)
    j = eqn.params.get("jaxpr")
    if j is not None and hasattr(j, "jaxpr"):
        for sub in j.jaxpr.eqns:
            names.append(sub.primitive.name)
    return tuple(names)


def _inner_eqn_count(eqn) -> int:
    """Equations inside a closed call, recursing through nested calls — a
    thin jit wrapper delegating to one big jitted helper is not glue."""
    def count(eqns) -> int:
        total = 0
        for e in eqns:
            total += 1
            for v in e.params.values():
                for sub in sim._sub_jaxpr(v):
                    total += count(sub.eqns)
        return total

    return sum(count(sub.eqns)
               for v in eqn.params.values() for sub in sim._sub_jaxpr(v))


def _is_glue(eqn, derived: set) -> bool:
    """Small closed calls, and calls none of whose inputs derive from the
    program's inputs (mask builders like a jitted ``tril`` over constants
    compute the same value every run), are glue: they fold into the
    surrounding run instead of splitting a matchable block.  Any
    input-derived operand — float activations or integer indices into a
    closed-over table — keeps the call a region of its own."""
    if eqn.primitive.name in _CONTROL_PRIMS:
        return False
    if _inner_eqn_count(eqn) <= _GLUE_MAX_EQNS:
        return True
    return not any(v in derived for v in eqn.invars if hasattr(v, "count"))


def build_graph(fn: Callable, *example_args, name: str = "") -> RegionGraph:
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    jaxpr = closed.jaxpr
    regions: list[Region] = []
    pending: list = []          # (eqn index, eqn)
    counter = 0

    # stable var naming by first appearance: str(Var) embeds the object id,
    # which would make def/use sets — and the graph fingerprint keying the
    # persistent measurement cache — differ between processes and traces
    _names: dict = {}

    def vname(v) -> str:
        return _names.setdefault(v, f"v{len(_names)}")

    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        vname(v)
    for e in jaxpr.eqns:
        for v in list(e.invars) + list(e.outvars):
            if hasattr(v, "count"):
                vname(v)

    def flush():
        nonlocal pending, counter
        if not pending:
            return
        eqns = [e for _, e in pending]
        defs = frozenset(vname(v) for e in eqns for v in e.outvars)
        uses = frozenset(vname(v) for e in eqns for v in e.invars
                         if hasattr(v, "count"))
        vec = sim.eqns_vector(eqns)
        # >= 5 equations = a "functional structure" worth pattern-matching
        # (paper Step1: 機能処理を分析); smaller runs are glue statements.
        is_block = len(eqns) >= 5
        regions.append(Region(
            name=f"{'block' if is_block else 'stmt'}_{counter}",
            kind="block" if is_block else "stmt",
            defs=defs, uses=uses,
            callees=tuple(e.primitive.name for e in eqns),
            feature_vector=vec, offloadable=is_block,
            alternatives=("ref", "kernel") if is_block else (),
            meta={"eqn_span": (pending[0][0], pending[-1][0] + 1)}))
        counter += 1
        pending = []

    # vars carrying data derived from the program inputs (vs masks/consts)
    derived: set = set(jaxpr.invars)
    for idx, eqn in enumerate(jaxpr.eqns):
        pname = eqn.primitive.name
        if any(v in derived for v in eqn.invars if hasattr(v, "count")):
            derived.update(eqn.outvars)
        if (pname in _LOOP_PRIMS or "call" in pname) \
                and not _is_glue(eqn, derived):
            flush()
            sub = eqn.params.get("jaxpr")
            vec = sim.jaxpr_vector(sub) if sub is not None else {}
            vec[pname] = vec.get(pname, 0) + 1
            trip = eqn.params.get("length")
            meta: dict = {"primitive": pname, "eqn_span": (idx, idx + 1)}
            if pname == "scan":
                meta["scan"] = {k: eqn.params.get(k)
                                for k in ("num_consts", "num_carry",
                                          "length", "reverse")}
            regions.append(Region(
                name=f"{'loop' if pname in ('scan', 'while') else 'block'}_{counter}",
                kind="loop" if pname in ("scan", "while") else "block",
                defs=frozenset(vname(v) for v in eqn.outvars),
                uses=frozenset(vname(v) for v in eqn.invars
                               if hasattr(v, "count")),
                callees=_eqn_callees(eqn),
                feature_vector=vec,
                offloadable=True,
                alternatives=("ref", "kernel"),
                trip_count=trip if isinstance(trip, int) else None,
                meta=meta,
            ))
            counter += 1
        else:
            pending.append((idx, eqn))
    flush()
    g = RegionGraph(regions, "jaxpr", name or getattr(fn, "__name__", "traced"))
    g.meta["whole_program_vector"] = sim.jaxpr_vector(closed)
    # the trace the eqn spans index, for the substitution engine: reusing it
    # avoids re-tracing and guarantees span alignment (in-memory only; the
    # fingerprint never hashes meta)
    g.meta["closed_jaxpr"] = closed
    g.meta["out_tree"] = jax.tree_util.tree_structure(out_shape)
    return g


def annotate_variants(graph: RegionGraph, db, registry=None) -> RegionGraph:
    """Match offloadable regions against the pattern DB and widen their
    implementation alternatives to the registry's executable variants.

    A matched region gets ``meta["pattern"]`` (the pattern-DB record name,
    what the substitution engine keys variants on) and
    ``alternatives = ("ref",) + variant names`` — so a gene over the variant
    alphabet (:data:`repro.core.genes.VARIANT_ALPHABET`) selects *which
    implementation runs*, not just placement.  Unmatched regions keep the
    legacy ``("ref", "kernel")`` pair.
    """
    from repro.kernels.registry import default_registry

    registry = registry or default_registry()
    for region in graph.offloadable():
        matches = db.match_region(region, graph.frontend)
        if not matches:
            continue
        m = matches[0]
        names = registry.variant_names(m.record.name)
        if not names:
            continue
        region.meta["pattern"] = m.record.name
        region.meta["pattern_match"] = {"how": m.how,
                                        "score": round(m.score, 4)}
        region.alternatives = ("ref",) + names
    return graph


def annotate_block_sites(graph: RegionGraph, db, registry=None) -> RegionGraph:
    """Detect *function-block* offload sites: maximal windows of adjacent
    offloadable regions whose merged shape matches a ``block`` pattern-DB
    record (arXiv 2004.09883's function-block genes alongside loop genes).

    Each accepted window becomes a synthetic ``fnblock_*`` region appended
    to the graph: one extra gene whose accelerated alternatives are the
    registry's *block-level* variants.  While that gene is active it claims
    its ``meta["block_members"]`` (see :class:`repro.core.genes.Site`), so
    the member regions' own genes go inert and the whole span runs through
    the block adapter.  The region carries empty def/use sets — the block
    substitutes *in place of* its members, so the transfer planner must not
    charge it extra traffic.

    Windows are tried widest-first and accepted greedily non-overlapping; a
    window is kept only if at least one registry variant actually binds the
    merged span's concrete avals (no dead genes in the chromosome).
    """
    from jax.extend.core import Literal

    from repro.core.substitution import _span_io
    from repro.core.variants import resolve_variant
    from repro.kernels.registry import CallSite, default_registry

    registry = registry or default_registry()
    closed = graph.meta.get("closed_jaxpr")
    if closed is None:
        return graph
    eqns = closed.jaxpr.eqns
    last_use: dict = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_use[v] = i
    program_outs = {v for v in closed.jaxpr.outvars
                    if not isinstance(v, Literal)}
    backend = jax.default_backend()

    # maximal runs of span-adjacent offloadable candidates, program order
    cands = [r for r in graph.regions
             if r.offloadable and r.meta.get("eqn_span") is not None
             and not r.meta.get("block_members")]
    runs: list[list[Region]] = []
    for r in cands:
        if runs and runs[-1][-1].meta["eqn_span"][1] == r.meta["eqn_span"][0]:
            runs[-1].append(r)
        else:
            runs.append([r])

    accepted: list[tuple[int, int]] = []
    blocks: list[Region] = []
    for run in runs:
        for width in range(len(run), 1, -1):
            for lo in range(len(run) - width + 1):
                members = run[lo:lo + width]
                s = members[0].meta["eqn_span"][0]
                e = members[-1].meta["eqn_span"][1]
                if any(s < e0 and s0 < e for s0, e0 in accepted):
                    continue
                m = db.match_block(members, graph.frontend)
                if m is None:
                    continue
                names = registry.variant_names(m.record.name)
                if not names:
                    continue

                def used_later(v, _e=e):
                    return v in program_outs or last_use.get(v, -1) >= _e

                ins, outs = _span_io(eqns[s:e], used_later)
                site = CallSite(
                    pattern=m.record.name, kind="block",
                    in_avals=tuple(v.aval for v in ins),
                    out_avals=tuple(v.aval for v in outs),
                    out_used=(True,) * len(outs), params={},
                    backend=backend, eqns=tuple(eqns[s:e]),
                    in_vars=tuple(ins))
                if not any(resolve_variant(site, n, registry=registry,
                                           backend=backend)[0] is not None
                           for n in names):
                    continue
                vec: dict = {}
                for r in members:
                    for k, c in r.feature_vector.items():
                        vec[k] = vec.get(k, 0) + c
                blocks.append(Region(
                    name=f"fnblock_{len(blocks)}",
                    kind="block",
                    defs=frozenset(), uses=frozenset(),
                    callees=tuple(dict.fromkeys(
                        c for r in members for c in r.callees)),
                    feature_vector=vec,
                    offloadable=True,
                    alternatives=("ref",) + names,
                    meta={"pattern": m.record.name,
                          "pattern_match": {"how": m.how,
                                            "score": round(m.score, 4)},
                          "eqn_span": (s, e),
                          "block_members": tuple(r.name for r in members)}))
                accepted.append((s, e))
    graph.regions.extend(blocks)
    return graph


# ---------------------------------------------------------------------------
# the Frontend adapter (repro.core.frontends.registry protocol)
# ---------------------------------------------------------------------------


class JaxprFrontend:
    """Traced-JAX frontend for the unified pipeline.

    ``options["example_args"]`` supplies the tracing arguments.  The default
    fitness is *measured*: every chromosome decodes to a substituted program
    (kernel registry variants spliced in by the substitution engine), which
    is jitted, verified against the unsubstituted reference
    (:mod:`repro.core.verifier` numeric equivalence) and wall-clock timed —
    the paper's verification-environment loop on real artifacts.  Pass
    ``options={"static_cost": True}`` to keep the deterministic transfer
    cost stub instead (the conformance-friendly no-execution path; results
    carry ``static_cost`` so they are never mistaken for measurements).
    """

    name = "jaxpr"

    def build_graph(self, fn: Callable, inputs, config) -> RegionGraph:
        from repro.core.pattern_db import default_db

        example_args = config.options.get("example_args", ())
        graph = build_graph(fn, *example_args,
                            name=config.options.get("name", ""))
        db = config.db or default_db()
        graph = annotate_variants(graph, db,
                                  registry=config.options.get("registry"))
        # function-block genes (whole-window substitution) ride alongside
        # the loop/span genes unless explicitly disabled with
        # options={"block_sites": False} (the loop-only comparison arm)
        if config.options.get("block_sites", True):
            graph = annotate_block_sites(
                graph, db, registry=config.options.get("registry"))
        return graph

    def make_fitness(self, graph: RegionGraph, fn: Callable, inputs, config):
        from repro.core.block_offload import block_offload_pass
        from repro.core.frontends.registry import (FitnessBundle,
                                                   static_cost_fitness_factory)
        from repro.core.pattern_db import default_db

        block = block_offload_pass(graph, config.db or default_db(),
                                   confirm=config.confirm)
        if config.options.get("static_cost"):
            return FitnessBundle(
                fitness_factory=static_cost_fitness_factory(graph),
                block=block, claimed=block.claimed_regions,
                base_impl={r: "kernel" for r in block.claimed_regions},
                cache_extra=f"jaxpr={graph.source_name}|staticcost",
                measured=False)

        import threading

        from repro.core.fitness import WallClockFitness
        from repro.core.frontends.registry import decoded_pattern
        from repro.core.genes import VARIANT_ALPHABET, with_mesh_destinations
        from repro.core.pattern_db import record_pattern_outcome
        from repro.core.substitution import SubstitutionEngine

        example_args = tuple(config.options.get("example_args", ()))
        engine = SubstitutionEngine(fn, example_args, graph,
                                    registry=config.options.get("registry"))
        reference_output = jax.tree_util.tree_map(
            lambda x: np.asarray(x) if hasattr(x, "dtype") else x,
            engine.reference())
        args_sig = ",".join(
            f"{tuple(np.shape(a))}:{getattr(a, 'dtype', np.dtype(type(a)))}"
            for a in jax.tree_util.tree_leaves(example_args))
        repeats = config.repeats
        precision_dir = config.ga.cache_dir

        def factory(coding):
            # bits -> SubstitutionReport of the program just built, so the
            # verifier outcome in prepare() can be attributed per (pattern,
            # variant).  Guarded: prepare may run on compile-pool threads.
            reports: dict = {}
            rlock = threading.Lock()

            def build(values):
                values = tuple(values)
                impl = decoded_pattern(coding, values, {})
                sub = engine.substitute(
                    impl, destinations=coding.destinations_of(values))
                with rlock:
                    reports[tuple(values)] = sub.report
                jitted = jax.jit(sub.fn)
                return lambda: jitted(*example_args)

            class _RecordingFitness(WallClockFitness):
                """Classify each chromosome's verifier outcome and journal
                it per substituted (pattern, variant) — the ROADMAP's
                per-pattern match-precision record."""

                def prepare(self, bits):
                    prep = super().prepare(tuple(bits))
                    with rlock:
                        report = reports.pop(tuple(bits), None)
                    if report is None:     # build itself failed: no program
                        return prep
                    if prep.failure is None:
                        outcome = "ok"
                    elif "verify" in prep.failure.detail:
                        outcome = "verify_fail"
                    else:
                        outcome = "error"
                    for c in report.choices:
                        if c.chosen != "ref":
                            record_pattern_outcome(
                                precision_dir, c.pattern, c.chosen,
                                outcome, region=c.region)
                        elif c.requested not in ("ref", "interp",
                                                 "host", "cpu"):
                            record_pattern_outcome(
                                precision_dir, c.pattern, c.requested,
                                "bind_fail", region=c.region)
                    return prep

            return _RecordingFitness(build, reference_output=reference_output,
                                     repeats=repeats)

        # note: block-pass matches are *not* claimed here — on the measured
        # path the genes range over each matched region's variant set (the
        # paper measures replacement blocks on/off too), so the GA decides
        # which implementation runs; the block result remains for reporting
        # and pattern-DB population seeding.
        return FitnessBundle(
            fitness_factory=factory,
            block=block, claimed=(), base_impl={},
            # device count joins the cache key: the same bits measured on
            # hosts of different sizes are different experiments
            cache_extra=(f"jaxpr={graph.source_name}|measured"
                         f"|args={args_sig}|backend={engine.backend}"
                         f"|ndev={jax.device_count()}"),
            serial_only=True, measured=True, overlap_compiles=True,
            # variant alphabet plus whatever meshes this host can really
            # build (empty extension on single-device CI); this measured
            # path decodes mesh genes to sharded execution
            destinations=with_mesh_destinations(VARIANT_ALPHABET),
            # bind results join the phenotype key: two chromosomes whose
            # variants fall back to ref at a site are one program and
            # share one measurement (eager resolution is static per
            # (region, impl) — the avals never change)
            impl_resolver=engine.resolved_impl,
            context={"engine": engine, "example_args": example_args})

    def apply_plan(self, graph: RegionGraph, coding, values, bundle):
        from repro.core.frontends.registry import decoded_pattern

        values = tuple(values)
        impl = decoded_pattern(coding, values, bundle.base_impl)
        engine = bundle.context.get("engine")
        if engine is None:               # static-cost path: impl map only
            return impl
        return engine.substitute(impl,
                                 destinations=coding.destinations_of(values))
