"""Implementation-variant registry: pattern-DB entries -> executable variants.

The paper's final step replaces matched functional blocks with library
implementations and measures the *converted* program.  This registry is the
library side of that step: each pattern-DB record name maps to an ordered set
of :class:`Variant`s — ``fused_jnp`` (a fused jax.numpy rewrite) and
``pallas`` (the Pallas kernel wrappers in :mod:`repro.kernels.ops`) — that
the jaxpr substitution engine (:mod:`repro.core.substitution`) can splice
into a traced program in place of the matched region.

A variant *binds* to a concrete call site: ``Variant.bind(site)`` inspects
the site's abstract values (shapes, dtypes, scan structure, which outputs
are actually used) and either returns an adapter callable whose outputs
match the site's output avals, or raises :class:`VariantUnavailable` with
the reason.  Binding is the availability predicate — anything a variant
cannot prove it handles from the avals falls back to the reference path,
and anything it handles *wrongly* (e.g. a non-causal attention matched to
the causal kernels) is caught by the per-measurement verifier, which is the
paper's PCAST flow doing its job.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp


class VariantUnavailable(Exception):
    """A variant's availability predicate rejected the call site."""


@dataclass(frozen=True)
class CallSite:
    """What a variant binds against: one matched region, concretized.

    ``kind`` is how the region appears in the jaxpr — a ``span`` of simple
    equations, a single closed ``call`` (jit), or a ``scan``.  ``in_avals``
    / ``out_avals`` follow the jaxpr equation/span order (for scans:
    ``[consts..., carry..., xs...]`` in, ``[carry..., ys...]`` out).
    ``out_used[i]`` is False when output ``i`` is dropped by the program —
    a variant that cannot produce an *unused* output may still bind.
    """

    pattern: str
    kind: str                          # "span" | "call" | "scan"
    in_avals: tuple
    out_avals: tuple
    out_used: tuple
    params: Mapping = field(default_factory=dict)   # scan: num_consts,
                                                    # num_carry, reverse
    backend: str = "cpu"
    eqns: tuple = ()                   # span sites: the intercepted
                                       # equations, for structural operand-
                                       # role inference (jaxpr input order
                                       # is first-use order, NOT call order)
    in_vars: tuple = ()                # span sites: vars aligned w/ in_avals


@dataclass(frozen=True)
class Variant:
    """One executable implementation of a pattern."""

    pattern: str                       # pattern-DB record name
    name: str                          # "fused_jnp" | "pallas" | custom
    bind: Callable[[CallSite], Callable[..., tuple]]
    description: str = ""

    def available(self, site: CallSite) -> bool:
        try:
            self.bind(site)
            return True
        except VariantUnavailable:
            return False


class KernelRegistry:
    """Ordered pattern -> variants store (registration order is preserved;
    it defines the gene-alphabet implementation order ``("ref",) + names``)."""

    def __init__(self) -> None:
        self._by_pattern: dict[str, dict[str, Variant]] = {}

    def register(self, variant: Variant, replace: bool = False) -> None:
        slot = self._by_pattern.setdefault(variant.pattern, {})
        if variant.name in slot and not replace:
            raise ValueError(f"variant {variant.pattern}:{variant.name} "
                             f"already registered")
        slot[variant.name] = variant

    def patterns(self) -> tuple[str, ...]:
        return tuple(self._by_pattern)

    def variants_for(self, pattern: str) -> tuple[Variant, ...]:
        return tuple(self._by_pattern.get(pattern, {}).values())

    def variant_names(self, pattern: str) -> tuple[str, ...]:
        return tuple(self._by_pattern.get(pattern, {}))

    def get(self, pattern: str, name: str) -> Variant:
        try:
            return self._by_pattern[pattern][name]
        except KeyError:
            raise KeyError(
                f"unknown variant {pattern}:{name}; registered for "
                f"{pattern!r}: {self.variant_names(pattern)}") from None


# ---------------------------------------------------------------------------
# binding helpers
# ---------------------------------------------------------------------------


def _require(cond: bool, why: str) -> None:
    if not cond:
        raise VariantUnavailable(why)


def _floats(avals) -> bool:
    return all(jnp.issubdtype(a.dtype, jnp.floating) for a in avals)


def _cast(x: jax.Array, aval) -> jax.Array:
    return x.astype(aval.dtype) if x.dtype != aval.dtype else x


# ---------------------------------------------------------------------------
# softmax_attention: causal attention block (span or closed call)
# ---------------------------------------------------------------------------


def _attention_roles(site: CallSite) -> tuple:
    """Indices of (q, k, v) among the site inputs.

    A span's inputs arrive in trace first-use order — ``q @ k.T`` traces
    ``transpose(k)`` before touching ``q``, so positional binding would
    swap the operands.  Trace each dot_general operand back to the unique
    span input it derives from: the first dot's lhs is q, its rhs is k,
    the last dot's rhs is v.  Closed calls keep the function's signature
    order (the name-matched ``attention(q, k, v)`` convention).
    """
    if site.kind != "span" or not site.eqns:
        return (0, 1, 2)
    dots = [e for e in site.eqns if e.primitive.name == "dot_general"]
    _require(len(dots) >= 2, "attention span needs score and output matmuls")
    producer = {o: e for e in site.eqns for o in e.outvars}
    inputs = set(site.in_vars)

    def sole_root(v, what: str):
        out, stack, seen = set(), [v], set()
        while stack:
            x = stack.pop()
            if not hasattr(x, "count") or x in seen:
                continue
            seen.add(x)
            if x in inputs:
                out.add(x)
            elif x in producer:
                stack.extend(producer[x].invars)
        _require(len(out) == 1, f"cannot identify the {what} operand")
        return next(iter(out))

    qv = sole_root(dots[0].invars[0], "q")
    kv = sole_root(dots[0].invars[1], "k")
    vv = sole_root(dots[-1].invars[1], "v")
    _require(len({qv, kv, vv}) == 3, "attention operands are entangled")
    index = {var: i for i, var in enumerate(site.in_vars)}
    return (index[qv], index[kv], index[vv])


def _attention_site(site: CallSite):
    _require(site.kind in ("span", "call"),
             f"attention binds span/call sites, not {site.kind}")
    _require(len(site.in_avals) == 3, "attention needs exactly (q, k, v)")
    _require(sum(site.out_used) == 1 and len(site.out_avals) >= 1,
             "attention produces one used output")
    roles = _attention_roles(site)
    q, k, v = (site.in_avals[i] for i in roles)
    _require(_floats((q, k, v)), "attention needs floating inputs")
    _require(q.ndim == k.ndim == v.ndim, "q/k/v rank mismatch")
    _require(q.ndim in (2, 4), "attention supports (S,D) or (B,S,H,D)")
    _require(k.shape == v.shape, "k/v shape mismatch")
    _require(q.shape[-1] == k.shape[-1], "q/k head-dim mismatch")
    _require(q.shape[-1] <= 512, "head dim too large for the kernels")
    out = site.out_avals[list(site.out_used).index(True)]
    _require(out.shape == q.shape[:-1] + (v.shape[-1],),
             "output shape is not attention-like")
    if q.ndim == 4:
        _require(q.shape[2] % k.shape[2] == 0, "Hq must be a multiple of Hkv")
        _require(q.shape[0] == k.shape[0], "batch mismatch")
    return q, k, v, out, roles


def _bind_attention_fused(site: CallSite):
    from repro.kernels import ref

    q_av, k_av, v_av, out_av, roles = _attention_site(site)
    scale = 1.0 / math.sqrt(q_av.shape[-1])

    if q_av.ndim == 2:
        def fn(*xs):
            q, k, v = (xs[i] for i in roles)
            o = ref.flash_attention_ref(q[None], k[None], v[None],
                                        causal=True, scale=scale)[0]
            return (_cast(o, out_av),)
    else:
        b, _, hq, d = q_av.shape
        hkv = k_av.shape[2]

        def fn(*xs):
            q, k, v = (xs[i] for i in roles)
            qf = q.transpose(0, 2, 1, 3).reshape(b * hq, q.shape[1], d)
            kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, k.shape[1], d)
            vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, v.shape[1], d)
            o = ref.flash_attention_ref(qf, kf, vf, causal=True, scale=scale,
                                        group=hq // hkv)
            o = o.reshape(b, hq, q.shape[1], d).transpose(0, 2, 1, 3)
            return (_cast(o, out_av),)
    return fn


def _bind_attention_pallas(site: CallSite):
    from repro.kernels import ops

    q_av, k_av, v_av, out_av, roles = _attention_site(site)
    _require(q_av.shape[-1] >= 2, "pallas flash needs head dim >= 2")

    if q_av.ndim == 2:
        def fn(*xs):
            q, k, v = (xs[i] for i in roles)
            o = ops.flash_attention(q[None, :, None, :], k[None, :, None, :],
                                    v[None, :, None, :], causal=True)
            return (_cast(o[0, :, 0, :], out_av),)
    else:
        def fn(*xs):
            q, k, v = (xs[i] for i in roles)
            return (_cast(ops.flash_attention(q, k, v, causal=True), out_av),)
    return fn


# ---------------------------------------------------------------------------
# rmsnorm: (x, scale) -> normalized x, (1 + scale) weighting
# ---------------------------------------------------------------------------


def _rmsnorm_site(site: CallSite):
    _require(site.kind in ("span", "call"),
             f"rmsnorm binds span/call sites, not {site.kind}")
    _require(len(site.in_avals) == 2, "rmsnorm needs exactly (x, scale)")
    _require(sum(site.out_used) == 1, "rmsnorm produces one used output")
    a, b = site.in_avals
    x_av, s_av = (a, b) if a.ndim >= b.ndim else (b, a)
    swapped = x_av is b
    _require(_floats((x_av, s_av)), "rmsnorm needs floating inputs")
    _require(s_av.ndim == 1 and x_av.ndim >= 1, "scale must be rank 1")
    _require(x_av.shape[-1] == s_av.shape[0], "scale must match last dim")
    out = site.out_avals[list(site.out_used).index(True)]
    _require(out.shape == x_av.shape, "output must be x-shaped")
    return x_av, s_av, out, swapped


def _bind_rmsnorm_fused(site: CallSite):
    from repro.kernels import ref

    _, _, out_av, swapped = _rmsnorm_site(site)

    def fn(a, b):
        x, s = (b, a) if swapped else (a, b)
        return (_cast(ref.rmsnorm_ref(x, s), out_av),)
    return fn


def _bind_rmsnorm_pallas(site: CallSite):
    from repro.kernels import ops

    x_av, _, out_av, swapped = _rmsnorm_site(site)
    _require(x_av.ndim >= 2, "pallas rmsnorm needs a row dimension")

    def fn(a, b):
        x, s = (b, a) if swapped else (a, b)
        return (_cast(ops.rmsnorm(x, s), out_av),)
    return fn


# ---------------------------------------------------------------------------
# linear_recurrence: scan of h = exp(log_a) * h + b, ys = h
# ---------------------------------------------------------------------------


def _recurrence_site(site: CallSite):
    _require(site.kind == "scan", "linear_recurrence binds scan sites")
    _require(site.params.get("num_consts") == 0
             and site.params.get("num_carry") == 1,
             "expected scan(carry, (log_a, b))")
    _require(not site.params.get("reverse"), "reverse scan unsupported")
    _require(len(site.in_avals) == 3, "expected (h0, log_a, b)")
    _require(len(site.out_avals) == 2, "expected (h_final, ys) outputs")
    h0, la, b = site.in_avals
    _require(_floats((h0, la, b)), "needs floating inputs")
    _require(la.shape == b.shape and la.ndim in (2, 3),
             "xs must be equal-shaped (S,D) or (S,B,D)")
    _require(h0.shape == la.shape[1:], "carry must match one timestep")
    ys = site.out_avals[1]
    _require(ys.shape == la.shape, "ys must be xs-shaped")
    return h0, la, b, site.out_avals


def _recurrence_fn(site: CallSite, kernel: Callable):
    """Shared adapter: time-major scan xs -> the (B,S,D) kernels and back.

    ``kernel(log_a, b, h0) -> hs`` over batch-major (B,S,D); the final carry
    is served from ``hs[:, -1]`` (valid because the pattern's ys *is* the
    carry), so a downstream use of the scan's carry output still works.
    """
    h0_av, la_av, _, out_avals = _recurrence_site(site)
    batched = la_av.ndim == 3          # (S,B,D) time-major

    def fn(h0, la, b):
        if batched:
            la_b, b_b, h0_b = (la.transpose(1, 0, 2), b.transpose(1, 0, 2), h0)
        else:
            la_b, b_b, h0_b = la[None], b[None], h0[None]
        hs = kernel(la_b, b_b, h0_b)
        carry = hs[:, -1] if batched else hs[0, -1]
        ys = hs.transpose(1, 0, 2) if batched else hs[0]
        return (_cast(carry, out_avals[0]) if site.out_used[0] else None,
                _cast(ys, out_avals[1]) if site.out_used[1] else None)
    return fn


def _bind_recurrence_fused(site: CallSite):
    from repro.kernels import ref

    def kernel(la, b, h0):
        b = b.astype(jnp.float32)          # the scan math is f32 anyway
        b = b.at[:, 0].add(jnp.exp(la[:, 0].astype(jnp.float32)) * h0)
        return ref.rglru_scan_ref(la, b)
    return _recurrence_fn(site, kernel)


def _bind_recurrence_pallas(site: CallSite):
    from repro.kernels import ops

    def kernel(la, b, h0):
        return ops.rglru_scan(la.astype(jnp.float32),
                              b.astype(jnp.float32),
                              h0.astype(jnp.float32))
    return _recurrence_fn(site, kernel)


# ---------------------------------------------------------------------------
# wkv_recurrence: scan of the RWKV6 state update with bonus u
# ---------------------------------------------------------------------------


def _wkv_site(site: CallSite):
    _require(site.kind == "scan", "wkv_recurrence binds scan sites")
    _require(site.params.get("num_consts") == 1
             and site.params.get("num_carry") == 1,
             "expected scan(u; state, (r, k, v, log_w))")
    _require(not site.params.get("reverse"), "reverse scan unsupported")
    _require(len(site.in_avals) == 6, "expected (u, s0, r, k, v, log_w)")
    _require(len(site.out_avals) == 2, "expected (s_final, ys) outputs")
    u, s0, r, k, v, lw = site.in_avals
    _require(_floats(site.in_avals), "needs floating inputs")
    _require(r.ndim == 2 and r.shape == k.shape == v.shape == lw.shape,
             "xs must be equal-shaped (S,D)")
    d = r.shape[1]
    _require(u.shape == (d,) and s0.shape == (d, d),
             "bonus (D,) and state (D,D) expected")
    _require(not site.out_used[0],
             "the kernels do not produce the final state")
    ys = site.out_avals[1]
    _require(ys.shape == r.shape, "ys must be (S,D)")
    return site.out_avals


def _bind_wkv_fused(site: CallSite):
    from repro.kernels import ref

    out_avals = _wkv_site(site)

    def fn(u, s0, r, k, v, lw):
        ys = ref.wkv6_ref(r[None], k[None], v[None], lw[None], u[None, None])
        return (None, _cast(ys[0], out_avals[1]))
    return fn


def _bind_wkv_pallas(site: CallSite):
    from repro.kernels import ops

    out_avals = _wkv_site(site)

    def fn(u, s0, r, k, v, lw):
        ys = ops.wkv6(r[None, :, None, :], k[None, :, None, :],
                      v[None, :, None, :], lw[None, :, None, :], u[None])
        return (None, _cast(ys[0, :, 0, :], out_avals[1]))
    return fn


# ---------------------------------------------------------------------------
# block-level variants (function-block offload, arXiv 2004.09883): one
# variant replaces a *merged multi-region span* — the whole algorithm, not a
# single loop.  Block sites arrive with ``kind == "block"`` and carry the
# concatenated top-level equations of every member region, so the binders
# infer operand roles by dataflow exactly like the span binders do.
# ---------------------------------------------------------------------------


def _input_roots(site: CallSite):
    """Dataflow helper: map any site-internal var to the set of site inputs
    it derives from (non-raising twin of ``_attention_roles``'s sole_root)."""
    producer = {o: e for e in site.eqns for o in e.outvars}
    inputs = set(site.in_vars)

    def roots(v) -> frozenset:
        out, stack, seen = set(), [v], set()
        while stack:
            x = stack.pop()
            if not hasattr(x, "count") or x in seen:
                continue
            seen.add(x)
            if x in inputs:
                out.add(x)
            elif x in producer:
                stack.extend(producer[x].invars)
        return frozenset(out)
    return roots


def _sole_rhs_dots(site: CallSite) -> list:
    """Top-level dot_generals whose rhs traces back to exactly ONE site
    input: the weight matmuls of a block (score/combine matmuls mix several
    inputs on the rhs and drop out).  Returns [(eqn, rhs_input_var), ...]
    in equation order — which is the program's weight-application order."""
    roots = _input_roots(site)
    out = []
    for e in site.eqns:
        if e.primitive.name != "dot_general":
            continue
        rr = roots(e.invars[1])
        if len(rr) == 1:
            out.append((e, next(iter(rr))))
    return out


def _scan_params(eqns, name: str, key: str):
    """Find a primitive param anywhere in a block, including inside the
    closed sub-jaxprs of member jit calls."""
    for e in eqns:
        if e.primitive.name == name:
            return e.params.get(key)
        for v in e.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                found = _scan_params(sub.eqns, name, key)
                if found is not None:
                    return found
    return None


# --- attention_stack: rmsnorm + q/k/v projections + causal attention -------


def _attention_stack_site(site: CallSite):
    _require(site.kind == "block",
             f"attention_stack binds merged block sites, not {site.kind}")
    _require(len(site.in_avals) == 5, "expected (x, scale, wq, wk, wv)")
    _require(sum(site.out_used) == 1,
             "attention stack produces one used output")
    _require(_floats(site.in_avals), "needs floating inputs")
    if site.eqns:
        projs = _sole_rhs_dots(site)
        _require(len(projs) == 3,
                 "expected exactly the q/k/v projection matmuls")
        index = {v: i for i, v in enumerate(site.in_vars)}
        w_idx = tuple(index[r] for _, r in projs)  # (wq, wk, wv)
        one_d = [i for i, a in enumerate(site.in_avals) if a.ndim == 1]
        _require(len(one_d) == 1, "expected one rank-1 rmsnorm scale")
        rest = set(range(5)) - set(w_idx) - {one_d[0]}
        _require(len(rest) == 1, "cannot identify the residual-stream input")
        x_i = rest.pop()
        roles = (x_i, one_d[0]) + w_idx            # (x, scale, wq, wk, wv)
    else:
        # no equations (the python_ast frontend): the site builder already
        # ordered the operands positionally; the shape checks below reject
        # a wrong assignment
        roles = (0, 1, 2, 3, 4)
    x_av, s_av, wq_av, wk_av, wv_av = (site.in_avals[i] for i in roles)
    _require(x_av.ndim == 2, "(S, d) residual stream expected")
    _require(s_av.ndim == 1, "expected one rank-1 rmsnorm scale")
    _require(wq_av.shape == wk_av.shape == wv_av.shape and wq_av.ndim == 2,
             "q/k/v projection weight shapes disagree")
    _require(wq_av.shape[0] == x_av.shape[1], "projection d_model mismatch")
    _require(s_av.shape[0] == x_av.shape[1], "scale must match d_model")
    dh = wq_av.shape[1]
    _require(2 <= dh <= 512, "head dim outside kernel range")
    out_av = site.out_avals[list(site.out_used).index(True)]
    _require(out_av.shape == (x_av.shape[0], dh),
             "output is not attention-shaped")
    return x_av, out_av, roles


def _bind_attention_stack_chunked(site: CallSite):
    from repro.kernels import ref
    from repro.models.attention import attend_chunked
    from repro.models.plan import ExecPlan

    x_av, out_av, roles = _attention_stack_site(site)
    s = x_av.shape[0]
    plan = ExecPlan(attn_impl="chunked", attn_kv_chunk=128,
                    compute_dtype=str(x_av.dtype))
    pos = jnp.arange(s, dtype=jnp.int32)

    def fn(*xs):
        x, sc, wq, wk, wv = (xs[i] for i in roles)
        xn = ref.rmsnorm_ref(x, sc)
        q, k, v = xn @ wq, xn @ wk, xn @ wv
        o = attend_chunked(q[None, :, None, :], k[None, :, None, :],
                           v[None, :, None, :], pos, pos, True, 0, plan)
        return (_cast(o[0, :, 0, :], out_av),)
    return fn


def _bind_attention_stack_fused(site: CallSite):
    from repro.kernels import ref

    x_av, out_av, roles = _attention_stack_site(site)
    scale = 1.0 / math.sqrt(out_av.shape[-1])

    def fn(*xs):
        x, sc, wq, wk, wv = (xs[i] for i in roles)
        xn = ref.rmsnorm_ref(x, sc)
        q, k, v = xn @ wq, xn @ wk, xn @ wv
        o = ref.flash_attention_ref(q[None], k[None], v[None],
                                    causal=True, scale=scale)[0]
        return (_cast(o, out_av),)
    return fn


# --- moe_dispatch: router + top-k dispatch + batched expert FFN ------------


def _moe_site(site: CallSite):
    _require(site.kind == "block",
             f"moe_dispatch binds merged block sites, not {site.kind}")
    _require(bool(site.eqns), "block site carries no equations")
    _require(len(site.in_avals) == 5,
             "expected (x, w_router, w_gate, w_up, w_down)")
    _require(sum(site.out_used) == 1, "moe dispatch produces one used output")
    _require(_floats(site.in_avals), "needs floating inputs")
    rank3 = [i for i, a in enumerate(site.in_avals) if a.ndim == 3]
    _require(len(rank3) == 3, "expected three (E,·,·) expert weight stacks")
    index = {v: i for i, v in enumerate(site.in_vars)}
    # expert weights in application order: gate, up, down
    w_order = [index[r] for _, r in _sole_rhs_dots(site)
               if index[r] in rank3]
    _require(len(w_order) == 3, "cannot order the expert weight matmuls")
    wg_i, wu_i, wd_i = w_order
    rank2 = [i for i, a in enumerate(site.in_avals) if a.ndim == 2]
    _require(len(rank2) == 2, "expected tokens (T,d) and router (d,E)")
    top_k = _scan_params(site.eqns, "top_k", "k")
    _require(top_k is not None, "no top-k routing found in the block")
    # the router weight has E columns; tokens have d columns
    wg_av = site.in_avals[wg_i]
    n_experts, d = wg_av.shape[0], wg_av.shape[1]
    a2, b2 = (site.in_avals[i] for i in rank2)
    if a2.shape[1] == n_experts and b2.shape[1] == d:
        wr_i, x_i = rank2
    else:
        _require(b2.shape[1] == n_experts and a2.shape[1] == d,
                 "cannot tell router weight from token matrix")
        x_i, wr_i = rank2
    roles = (x_i, wr_i, wg_i, wu_i, wd_i)
    x_av = site.in_avals[x_i]
    _require(site.in_avals[wu_i].shape == wg_av.shape,
             "gate/up expert shapes disagree")
    _require(site.in_avals[wd_i].shape == (n_experts, wg_av.shape[2], d),
             "down projection shape mismatch")
    out_av = site.out_avals[list(site.out_used).index(True)]
    _require(out_av.shape == x_av.shape, "moe output must be token-shaped")
    return x_av, out_av, roles, n_experts, int(top_k)


def _bind_moe_scatter(site: CallSite):
    from repro.configs.base import ArchConfig, MoEConfig
    from repro.models.moe import moe_scatter
    from repro.models.plan import ExecPlan

    x_av, out_av, roles, n_experts, top_k = _moe_site(site)
    ff = site.in_avals[roles[2]].shape[2]
    # capacity_factor = E makes the dispatch dropless (cap = T*k), so the
    # scatter route is numerically the dense one-hot reference
    cfg = ArchConfig("block_moe", "moe", d_model=x_av.shape[1],
                     moe=MoEConfig(n_experts=n_experts, top_k=top_k,
                                   d_ff_expert=ff,
                                   capacity_factor=float(n_experts)))
    plan = ExecPlan(moe_impl="scatter_ep", compute_dtype=str(x_av.dtype))

    def fn(*xs):
        x, wr, wg, wu, wd = (xs[i] for i in roles)
        params = {"w_router": wr, "w_gate": wg, "w_up": wu, "w_down": wd}
        out, _aux = moe_scatter(x, params, cfg, plan)
        return (_cast(out, out_av),)
    return fn


# ---------------------------------------------------------------------------
# the default registry
# ---------------------------------------------------------------------------

_DEFAULT: Optional[KernelRegistry] = None


def default_registry() -> KernelRegistry:
    """The shipped variants; built once (registration order defines the
    ``("ref", "fused_jnp", "pallas")`` gene-implementation order)."""
    global _DEFAULT
    if _DEFAULT is not None:
        return _DEFAULT
    reg = KernelRegistry()
    for pattern, fused, pallas in (
        ("softmax_attention", _bind_attention_fused, _bind_attention_pallas),
        ("rmsnorm", _bind_rmsnorm_fused, _bind_rmsnorm_pallas),
        ("linear_recurrence", _bind_recurrence_fused, _bind_recurrence_pallas),
        ("wkv_recurrence", _bind_wkv_fused, _bind_wkv_pallas),
    ):
        reg.register(Variant(pattern, "fused_jnp", fused,
                             "fused jax.numpy rewrite"))
        reg.register(Variant(pattern, "pallas", pallas,
                             "Pallas kernel (repro.kernels.ops)"))
    # block-level patterns: whole-algorithm replacements over merged spans.
    # Registration order is the gene implementation order, so the flash-style
    # chunked route sits at impl_index 1 (the primary accelerated slot).
    reg.register(Variant("attention_stack", "block_chunked",
                         _bind_attention_stack_chunked,
                         "rmsnorm + QKV + flash attention via "
                         "models/attention.attend_chunked"))
    reg.register(Variant("attention_stack", "block_fused",
                         _bind_attention_stack_fused,
                         "rmsnorm + QKV + naive causal attention"))
    reg.register(Variant("moe_dispatch", "block_scatter",
                         _bind_moe_scatter,
                         "capacity-limited scatter dispatch via "
                         "models/moe.moe_scatter"))
    _DEFAULT = reg
    return reg


def auto_variant_order(backend: str) -> tuple[str, ...]:
    """Preference order for the legacy ``"kernel"`` (auto) implementation:
    the Pallas kernels on real TPU, the fused rewrites elsewhere (Pallas
    interpret mode is a correctness path, not a fast one)."""
    return ("pallas", "fused_jnp") if backend == "tpu" \
        else ("fused_jnp", "pallas")
