"""Jaxpr kernel-substitution engine: plans become runnable programs.

The paper's pipeline ends with *converted code* — matched functional blocks
replaced by library implementations, the converted program measured and
verified on the target.  This module closes that loop for the jaxpr
frontend: given the traced program, its :class:`~repro.core.ir.RegionGraph`
(whose regions carry equation spans from the frontend) and a
region -> implementation map decoded from a chromosome, it re-emits the
program with each matched region routed through the chosen variant from the
kernel registry (:mod:`repro.kernels.registry`).

Interception is equation-group based: the engine walks the jaxpr in program
order, and at a substituted region's span it feeds the span's free inputs to
the variant's bound adapter and binds the adapter's outputs to the span's
outputs, skipping the original equations; everything else executes through
``primitive.bind`` exactly as ``jax.core.eval_jaxpr`` would.  Variant
binding happens *eagerly* against the jaxpr's abstract values (plus an
``eval_shape`` output check), so every fallback decision is recorded in the
:class:`SubstitutionReport` before anything runs — and a variant whose
predicate rejects the concrete shapes silently degrades to the reference
equations instead of failing.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
from jax.core import DropVar
from jax.extend.core import Literal

from repro.core.ir import RegionGraph
# the resolve/check/fallback rule and the report types are frontend-neutral
# (repro.core.variants) — re-exported here for compatibility with PR-3 users
from repro.core.variants import (_REF_IMPLS, SubstitutionChoice,  # noqa: F401
                                 SubstitutionReport, check_adapter,
                                 resolve_variant)
from repro.kernels.registry import (CallSite, KernelRegistry,
                                    default_registry)

__all__ = ["SiteBinding", "SubstitutionChoice", "SubstitutionReport",
           "SubstitutedCallable", "SubstitutionEngine"]


class SubstitutedCallable:
    """A runnable substituted program: same signature as the traced source.

    ``fn`` is the raw (traceable) callable; calling the object runs a
    cached ``jax.jit`` of it.  ``report`` says which regions were
    substituted with which variant and why the rest fell back.
    """

    def __init__(self, fn: Callable, report: SubstitutionReport,
                 name: str = "substituted"):
        self.fn = fn
        self.report = report
        self.name = name
        self._jitted: Optional[Callable] = None

    def __call__(self, *args):
        if self._jitted is None:
            self._jitted = jax.jit(self.fn)
        return self._jitted(*args)

    def __repr__(self) -> str:
        return (f"SubstitutedCallable({self.name!r}, "
                f"substituted={self.report.substituted}, "
                f"fallbacks={list(self.report.fallbacks)})")


# ---------------------------------------------------------------------------
# sites: regions concretized against the jaxpr
# ---------------------------------------------------------------------------


@dataclass
class SiteBinding:
    """One substitutable region resolved to jaxpr vars."""

    region: str
    pattern: Optional[str]
    kind: str                          # "span" | "call" | "scan" | "block"
    span: tuple                       # (start, end) eqn indices
    in_vars: tuple                     # free inputs (first-use order for spans)
    out_vars: tuple                    # outputs (DropVar-preserving for eqns)
    params: dict = field(default_factory=dict)

    def call_site(self, out_used: Sequence[bool], backend: str,
                  eqns: tuple = ()) -> CallSite:
        return CallSite(
            pattern=self.pattern or "",
            kind=self.kind,
            in_avals=tuple(v.aval for v in self.in_vars),
            out_avals=tuple(v.aval for v in self.out_vars),
            out_used=tuple(out_used),
            params=dict(self.params),
            backend=backend,
            eqns=tuple(eqns),
            in_vars=tuple(self.in_vars))


def _eval_eqns(eqns: Sequence, env: dict) -> None:
    """Bind each equation on the values in ``env`` (var -> value), as
    ``jax.core.eval_jaxpr`` does, writing the outputs back into ``env``."""
    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    for eqn in eqns:
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        ans = eqn.primitive.bind(*subfuns, *[read(v) for v in eqn.invars],
                                 **bind_params)
        outs = ans if eqn.primitive.multiple_results else [ans]
        for v, a in zip(eqn.outvars, outs):
            if not isinstance(v, DropVar):
                env[v] = a


def _span_io(eqns: Sequence, used_later: Callable) -> tuple[tuple, tuple]:
    """Free inputs (first-use order) and live outputs of an equation group."""
    defined: set = set()
    ins: list = []
    for eqn in eqns:
        for v in eqn.invars:
            if isinstance(v, Literal) or v in defined or v in ins:
                continue
            ins.append(v)
        defined.update(o for o in eqn.outvars
                       if not isinstance(o, DropVar))
    outs = [o for eqn in eqns for o in eqn.outvars
            if not isinstance(o, DropVar) and used_later(o)]
    return tuple(ins), tuple(outs)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class SubstitutionEngine:
    """Re-emit a traced function with matched regions routed to variants.

    The graph must come from the jaxpr frontend with ``meta["eqn_span"]``
    populated.  The frontend's own trace (``graph.meta["closed_jaxpr"]`` /
    ``["out_tree"]``) is reused when present — the spans then index this
    engine's jaxpr by construction; otherwise ``fn`` is re-traced with the
    same example arguments.
    """

    def __init__(self, fn: Callable, example_args: tuple,
                 graph: RegionGraph,
                 registry: Optional[KernelRegistry] = None,
                 backend: Optional[str] = None):
        self.fn = fn
        self.example_args = tuple(example_args)
        self.graph = graph
        self.registry = registry or default_registry()
        self.backend = backend or jax.default_backend()
        self.closed = graph.meta.get("closed_jaxpr")
        self._out_tree = graph.meta.get("out_tree")
        if self.closed is None or self._out_tree is None:
            self.closed, out_shape = jax.make_jaxpr(
                fn, return_shape=True)(*self.example_args)
            self._out_tree = jax.tree_util.tree_structure(out_shape)
        self._sites = self._resolve_sites()
        self._reference: Any = None
        self._resolved: dict = {}      # (region, requested) -> resolution
        self._mesh_resolved: dict = {}  # (region, mesh name) -> (adapter, why)

    # -- site resolution ----------------------------------------------------

    def _resolve_sites(self) -> list[SiteBinding]:
        jaxpr = self.closed.jaxpr
        eqns = jaxpr.eqns
        # var -> last eqn index that reads it (or +inf if a program output)
        last_use: dict = {}
        for i, eqn in enumerate(eqns):
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    last_use[v] = i
        program_outs = {v for v in jaxpr.outvars
                        if not isinstance(v, Literal)}

        sites: list[SiteBinding] = []
        for region in self.graph.offloadable():
            span = region.meta.get("eqn_span")
            if span is None:
                continue
            s, e = span
            if not (0 <= s < e <= len(eqns)):
                continue
            pattern = region.meta.get("pattern")
            if e - s == 1 and region.meta.get("primitive"):
                # a loop/call region wrapping exactly one closed equation
                eqn = eqns[s]
                pname = eqn.primitive.name
                kind = "scan" if pname == "scan" else "call"
                params = {}
                if kind == "scan":
                    params = {k: eqn.params.get(k)
                              for k in ("num_consts", "num_carry", "length",
                                        "reverse")}
                sites.append(SiteBinding(
                    region.name, pattern, kind, (s, e),
                    in_vars=tuple(v for v in eqn.invars
                                  if not isinstance(v, Literal)),
                    out_vars=tuple(eqn.outvars), params=params))
            else:
                def used_later(v, _e=e):
                    return v in program_outs or last_use.get(v, -1) >= _e
                ins, outs = _span_io(eqns[s:e], used_later)
                # fnblock regions (merged multi-region spans from the block
                # pass) bind block-level variants; plain spans stay spans
                kind = "block" if region.meta.get("block_members") else "span"
                sites.append(SiteBinding(
                    region.name, pattern, kind, (s, e), ins, outs))
        return sites

    @property
    def sites(self) -> tuple[SiteBinding, ...]:
        return tuple(self._sites)

    # -- variant resolution -------------------------------------------------

    def _out_used(self, site: SiteBinding) -> list[bool]:
        if site.kind in ("span", "block"):
            return [True] * len(site.out_vars)   # spans keep live outs only
        jaxpr = self.closed.jaxpr
        last_use: set = set()
        for eqn in jaxpr.eqns[site.span[1]:]:
            last_use.update(v for v in eqn.invars
                            if not isinstance(v, Literal))
        last_use.update(v for v in jaxpr.outvars
                        if not isinstance(v, Literal))
        return [not isinstance(v, DropVar) and v in last_use
                for v in site.out_vars]

    def _resolve_variant(self, site: SiteBinding, requested: str
                         ) -> tuple[Optional[Callable], str, str]:
        """-> (adapter or None, chosen name, why).  Resolution depends only
        on (region, requested) for the engine's lifetime, and substitute()
        runs once per GA chromosome — memoized."""
        key = (site.region, requested)
        hit = self._resolved.get(key)
        if hit is not None:
            return hit
        self._resolved[key] = out = self._resolve_variant_uncached(
            site, requested)
        return out

    def _resolve_variant_uncached(self, site: SiteBinding, requested: str
                                  ) -> tuple[Optional[Callable], str, str]:
        """Concretize the site to a CallSite and apply the shared
        frontend-neutral resolution rule (repro.core.variants)."""
        if requested not in _REF_IMPLS and site.pattern is not None:
            out_used = self._out_used(site)
            eqns = self.closed.jaxpr.eqns[site.span[0]:site.span[1]] \
                if site.kind in ("span", "block") else ()
            call_site = site.call_site(out_used, self.backend, eqns=eqns)
        else:                          # resolution needs no concretization
            call_site = site.call_site([True] * len(site.out_vars),
                                       self.backend)
        return resolve_variant(call_site, requested, registry=self.registry,
                               backend=self.backend)

    # -- mesh destinations --------------------------------------------------

    def _mesh_adapter(self, site: SiteBinding, dest
                      ) -> tuple[Optional[Callable], str]:
        """-> (mesh-placed span adapter or None, why).  Memoized: the
        decision depends only on (site, mesh destination) for the engine's
        lifetime — avals and the device set are fixed."""
        key = (site.region, dest.name)
        hit = self._mesh_resolved.get(key)
        if hit is not None:
            return hit
        self._mesh_resolved[key] = out = self._mesh_adapter_uncached(site,
                                                                     dest)
        return out

    def _mesh_adapter_uncached(self, site: SiteBinding, dest
                               ) -> tuple[Optional[Callable], str]:
        """Build the genuine mesh execution of a site: the span's own
        equations, with their inputs and outputs constrained to a sharding
        over an n-device mesh, so the compiler partitions the span across
        those devices.

        The sharding heuristic is deliberately conservative and shape-
        checked: the destination's spec names a dimension (batch = leading,
        feature = trailing), every output must carry the same extent on it,
        inputs that carry it are sharded and the rest replicated.  Anything
        the heuristic cannot place — or that fails to trace — falls back to
        the normal variant path with the reason reported.  The equations
        keep their global shapes, so the placement changes where the span
        runs, never what it computes."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_destination_mesh

        if any(isinstance(v, DropVar) for v in site.out_vars):
            return None, "site has dropped outputs"
        out_shapes = [tuple(getattr(v.aval, "shape", ()))
                      for v in site.out_vars]
        if not out_shapes:
            return None, "site has no outputs"
        dim = dest.shard_dim

        def dim_of(shape: tuple) -> Optional[int]:
            d = dim + len(shape) if dim < 0 else dim
            return d if 0 <= d < len(shape) else None

        d0 = dim_of(out_shapes[0])
        if d0 is None:
            return None, "output lacks the sharded dimension"
        extent = out_shapes[0][d0]
        if extent == 0 or extent % dest.n != 0:
            return None, (f"output dim {extent} not divisible by "
                          f"n={dest.n}")
        for shape in out_shapes:
            d = dim_of(shape)
            if d is None or shape[d] != extent:
                return None, "outputs disagree on the sharded dimension"

        def spec_for(shape: tuple):
            d = dim_of(shape)
            if d is not None and shape[d] == extent:
                parts: list = [None] * len(shape)
                parts[d] = dest.axis
                return P(*parts)
            return P()

        in_specs = tuple(spec_for(tuple(getattr(v.aval, "shape", ())))
                         for v in site.in_vars)
        if all(sp == P() for sp in in_specs):
            return None, "no input carries the sharded dimension"

        eqns = tuple(self.closed.jaxpr.eqns[site.span[0]:site.span[1]])
        in_vars, out_vars = tuple(site.in_vars), tuple(site.out_vars)
        try:
            mesh = make_destination_mesh(dest.n, dest.axis)
        except ValueError as e:
            return None, str(e)
        in_sh = tuple(NamedSharding(mesh, sp) for sp in in_specs)
        out_sh = tuple(NamedSharding(mesh, spec_for(s)) for s in out_shapes)
        constrain = jax.lax.with_sharding_constraint

        def placed(*ins):
            env: dict = {v: constrain(x, sh)
                         for v, x, sh in zip(in_vars, ins, in_sh)}
            _eval_eqns(eqns, env)
            return tuple(constrain(env[v], sh)
                         for v, sh in zip(out_vars, out_sh))

        try:
            jax.eval_shape(placed, *[jax.ShapeDtypeStruct(v.aval.shape,
                                                          v.aval.dtype)
                                     for v in in_vars])
        except Exception as e:  # noqa: BLE001 — any trace-time rejection
            return None, f"mesh placement failed: {type(e).__name__}: {e}"
        return placed, (f"sharded over a {dest.n}-device {dest.axis!r} "
                        f"mesh (spec {dest.spec})")

    # -- substitution -------------------------------------------------------

    def substitute(self, impl: dict,
                   destinations: Optional[dict] = None
                   ) -> SubstitutedCallable:
        """``impl``: region -> implementation id ("ref", a variant name, or
        the legacy "kernel" auto choice).  Returns the runnable program.

        ``destinations`` (region -> destination name, from
        :meth:`GeneCoding.destinations_of`) routes mesh-assigned sites
        through :meth:`_mesh_adapter`: on hosts with enough devices the
        site's span genuinely runs sharded over the mesh; otherwise (or
        when the heuristic rejects the shapes) the site falls back to the
        normal variant resolution with the reason reported."""
        from repro.core.genes import get_destination, probed_device_count

        report = SubstitutionReport()
        actions: dict[int, tuple[SiteBinding, Callable]] = {}
        skip_until: dict[int, int] = {}
        # widest-first: when a block site substitutes, its adapter computes
        # the whole merged span — member sites inside it are claimed and any
        # variant requested on them falls back to ref (reported as such)
        accepted: list[tuple[int, int, str]] = []
        for site in sorted(self._sites,
                           key=lambda s: s.span[0] - s.span[1]):
            requested = str(impl.get(site.region, "ref"))
            owner = next((r for s0, e0, r in accepted
                          if site.span[0] < e0 and s0 < site.span[1]), None)
            if owner is not None:
                report.choices.append(SubstitutionChoice(
                    site.region, site.pattern, requested, "ref",
                    f"claimed by block {owner}"))
                continue
            dname = (destinations or {}).get(site.region)
            if dname and dname.startswith("mesh:"):
                mesh_dest = get_destination(dname)
                if not mesh_dest.available():
                    # a stored plan from a larger host: run the variant
                    adapter, chosen, why = self._resolve_variant(site,
                                                                 requested)
                    why = (f"mesh {mesh_dest.name!r} unavailable "
                           f"({probed_device_count()} device(s) < "
                           f"{mesh_dest.n}); {why}")
                else:
                    adapter, mesh_why = self._mesh_adapter(site, mesh_dest)
                    if adapter is not None:
                        chosen, why = mesh_dest.name, mesh_why
                    else:
                        adapter, chosen, why = self._resolve_variant(
                            site, requested)
                        why = (f"mesh {mesh_dest.name!r} rejected "
                               f"({mesh_why}); {why}")
                report.choices.append(SubstitutionChoice(
                    site.region, site.pattern, mesh_dest.name, chosen, why))
            else:
                adapter, chosen, why = self._resolve_variant(site, requested)
                report.choices.append(SubstitutionChoice(
                    site.region, site.pattern, requested, chosen, why))
            if adapter is not None:
                actions[site.span[0]] = (site, adapter)
                skip_until[site.span[0]] = site.span[1]
                accepted.append((site.span[0], site.span[1], site.region))

        closed, out_tree = self.closed, self._out_tree
        n_in = len(closed.jaxpr.invars)

        def run(*args):
            flat = jax.tree_util.tree_leaves(args)
            if len(flat) != n_in:
                raise TypeError(f"expected {n_in} input leaves, got "
                                f"{len(flat)}")
            jaxpr = closed.jaxpr
            env: dict = {}

            def read(v):
                return v.val if isinstance(v, Literal) else env[v]

            def write(v, val):
                if not isinstance(v, DropVar):
                    env[v] = val

            for v, c in zip(jaxpr.constvars, closed.consts):
                env[v] = c
            for v, a in zip(jaxpr.invars, flat):
                env[v] = a

            i = 0
            eqns = jaxpr.eqns
            while i < len(eqns):
                act = actions.get(i)
                if act is not None:
                    site, adapter = act
                    outs = adapter(*[read(v) for v in site.in_vars])
                    for v, o in zip(site.out_vars, outs):
                        if o is not None:
                            write(v, o)
                    i = skip_until[i]
                    continue
                eqn = eqns[i]
                subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
                ans = eqn.primitive.bind(
                    *subfuns, *[read(v) for v in eqn.invars], **bind_params)
                if eqn.primitive.multiple_results:
                    for v, a in zip(eqn.outvars, ans):
                        write(v, a)
                else:
                    write(eqn.outvars[0], ans)
                i += 1

            outvals = [read(v) for v in jaxpr.outvars]
            return jax.tree_util.tree_unflatten(out_tree, outvals)

        return SubstitutedCallable(run, report, self.graph.source_name)

    # -- convenience --------------------------------------------------------

    def resolved_impl(self, region: str, impl_id) -> str:
        """The implementation that would actually run at ``region`` under
        ``impl_id``, after the eager bind/fallback rule — ``"ref"`` when
        the variant cannot bind (or the region has no site).  This is the
        frontend's contribution to the phenotype key: two plans whose
        variants both fall back at a site are the same program and share
        one measurement.  Resolution is memoized per (region, impl) and
        static for the engine's lifetime (avals are fixed)."""
        requested = str(impl_id)
        site = next((s for s in self._sites if s.region == region), None)
        if site is None:
            # substitute() leaves regions without a site untouched — any
            # requested impl there runs the reference path
            return "ref"
        _adapter, chosen, _why = self._resolve_variant(site, requested)
        return chosen

    def _site_values(self, site: SiteBinding) -> tuple[list, list]:
        """One reference interpretation up to the site's span end, capturing
        the concrete values of its free inputs and live outputs."""
        closed = self.closed
        jaxpr = closed.jaxpr
        flat = jax.tree_util.tree_leaves(self.example_args)
        env: dict = dict(zip(jaxpr.constvars, closed.consts))
        env.update(zip(jaxpr.invars, flat))
        _eval_eqns(jaxpr.eqns[:site.span[1]], env)
        return ([v.val if isinstance(v, Literal) else env[v]
                 for v in site.in_vars],
                [env.get(v) for v in site.out_vars])

    def verify_block(self, region: str, impl_id,
                     rtol: float = 1e-2, atol: float = 1e-2):
        """Block-granularity verification: allclose of the bound adapter's
        outputs against the reference interpretation *over the whole span*
        (not just the program outputs), on the example arguments.  Returns
        ``(VerifyResult, chosen_impl)``; a predicate rejection verifies
        trivially as the reference path with ``chosen == "ref"``."""
        from repro.core.verifier import VerifyResult, verify as _verify

        from repro.core.pattern_db import record_pattern_outcome

        site = next((s for s in self._sites if s.region == region), None)
        if site is None:
            raise KeyError(f"no substitutable site for region {region!r}")
        adapter, chosen, why = self._resolve_variant(site, str(impl_id))
        if adapter is None:
            if chosen == "ref" and str(impl_id) not in ("ref", "interp",
                                                        "host", "cpu"):
                record_pattern_outcome(None, site.pattern, str(impl_id),
                                       "bind_fail", region=region)
            return VerifyResult(True, 0.0, 0.0, why), chosen
        ins, ref_outs = self._site_values(site)
        got = adapter(*ins)
        used = self._out_used(site)
        ref_used = [o for o, u in zip(ref_outs, used) if u]
        got_used = [o for o, u in zip(got, used) if u]
        res = _verify(ref_used, got_used, rtol=rtol, atol=atol)
        record_pattern_outcome(None, site.pattern, chosen,
                               "ok" if res.ok else "verify_fail",
                               region=region)
        return res, chosen

    def reference(self) -> Any:
        """The unsubstituted program's outputs on the example arguments
        (computed once, then cached)."""
        if self._reference is None:
            self._reference = self.fn(*self.example_args)
        return self._reference

    def verify(self, impl, rtol: float = 1e-2, atol: float = 1e-2):
        """Numeric equivalence of a substituted program vs the reference
        (:func:`repro.core.verifier.verify`).  ``impl`` is a region -> impl
        map, or a :class:`SubstitutedCallable` already built from one."""
        from repro.core.verifier import verify as _verify

        sub = impl if isinstance(impl, SubstitutedCallable) \
            else self.substitute(impl)
        return _verify(self.reference(), sub(*self.example_args),
                       rtol=rtol, atol=atol)
