"""Deckard-style structural similarity (paper §3.2.2: 類似性検出ツール).

Deckard (ICSE'07) maps AST subtrees to *characteristic vectors* of node-type
counts and clusters near vectors.  We retarget the exact algorithm at our two
IRs:

  * Python ``ast`` subtrees  -> counts of ast node types      (CloneDigger role)
  * ``jaxpr`` equation lists -> counts of primitive names     (Deckard role)

Similarity = cosine between count vectors; a match needs similarity >= the
pattern's threshold.  This catches "copied then modified" implementations
that exact name matching misses — e.g. a hand-written softmax-attention with
an extra mask still matches the flash-attention pattern at ~0.9.
"""
from __future__ import annotations

import ast as pyast
import math
from collections import Counter
from typing import Any, Callable

import jax
import numpy as np


# ---------------------------------------------------------------------------
# characteristic vectors
# ---------------------------------------------------------------------------


_CALL_WEIGHT = 6   # call identities discriminate far better than node types


def ast_vector(node: pyast.AST) -> dict[str, int]:
    """Characteristic vector over a Python AST subtree.

    Features: node-type counts, weighted call names (cos/exp/dot identify a
    block much more strongly than generic loop scaffolding), binary-op kinds,
    and a loop-nesting histogram (Deckard's stratified vectors analogue).
    """
    counts: Counter = Counter()

    def walk(n: pyast.AST, loop_depth: int) -> None:
        counts[type(n).__name__] += 1
        if isinstance(n, pyast.Call):
            name = _call_name(n)
            if name:
                counts[f"call:{name.split('.')[-1]}"] += _CALL_WEIGHT
        if isinstance(n, pyast.BinOp):
            counts[f"op:{type(n.op).__name__}"] += 1
        d = loop_depth
        if isinstance(n, (pyast.For, pyast.While)):
            counts[f"nest:{loop_depth}"] += 2
            d += 1
        for c in pyast.iter_child_nodes(n):
            walk(c, d)

    walk(node, 0)
    return dict(counts)


def _call_name(node: pyast.Call) -> str:
    f = node.func
    parts: list[str] = []
    while isinstance(f, pyast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, pyast.Name):
        parts.append(f.id)
    return ".".join(reversed(parts))


#: dtype plumbing, not structure: counting these would make the same block
#: in bf16 look unlike its f32 comparison code.
_IGNORED_PRIMS = {"convert_element_type"}


def jaxpr_vector(jaxpr: Any) -> dict[str, int]:
    """Primitive counts over a (Closed)Jaxpr, recursing into sub-jaxprs."""
    counts: Counter = Counter()

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name not in _IGNORED_PRIMS:
                counts[eqn.primitive.name] += 1
            for v in eqn.params.values():
                inner = _sub_jaxpr(v)
                for sub in inner:
                    walk(sub)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return dict(counts)


def eqns_vector(eqns: Any) -> dict[str, int]:
    """Primitive counts over a list of jaxpr equations, recursing into
    sub-jaxprs (glue calls like a jitted ``tril`` contribute their inner
    primitives, so region vectors stay comparable with the whole-trace
    vectors the pattern DB stores)."""
    counts: Counter = Counter()

    def walk_eqns(es):
        for eqn in es:
            if eqn.primitive.name not in _IGNORED_PRIMS:
                counts[eqn.primitive.name] += 1
            for v in eqn.params.values():
                for sub in _sub_jaxpr(v):
                    walk_eqns(sub.eqns)

    walk_eqns(eqns)
    return dict(counts)


def _sub_jaxpr(v: Any) -> list:
    out = []
    if hasattr(v, "jaxpr"):        # ClosedJaxpr
        out.append(v.jaxpr)
    elif hasattr(v, "eqns"):       # Jaxpr
        out.append(v)
    elif isinstance(v, (list, tuple)):
        for x in v:
            out.extend(_sub_jaxpr(x))
    return out


def vector_of_callable(fn: Callable, *example_args) -> dict[str, int]:
    """Trace a callable to a jaxpr and take its characteristic vector."""
    jx = jax.make_jaxpr(fn)(*example_args)
    return jaxpr_vector(jx)


# ---------------------------------------------------------------------------
# similarity
# ---------------------------------------------------------------------------


def cosine(a: dict[str, int], b: dict[str, int]) -> float:
    if not a or not b:
        return 0.0
    keys = set(a) | set(b)
    va = np.array([a.get(k, 0) for k in keys], dtype=np.float64)
    vb = np.array([b.get(k, 0) for k in keys], dtype=np.float64)
    na, nb = np.linalg.norm(va), np.linalg.norm(vb)
    if na == 0 or nb == 0:
        return 0.0
    return float(va @ vb / (na * nb))


def similarity(a: dict[str, int], b: dict[str, int]) -> float:
    """Cosine over characteristic vectors (Deckard uses euclidean LSH; cosine
    is scale-invariant which suits loop-trip-count differences)."""
    return cosine(a, b)


def graph_vector(graph) -> dict[str, int]:
    """Whole-program characteristic vector of a RegionGraph: the sum of the
    regions' vectors plus weighted callee names — what the offload seed bank
    compares to find *near*-identical programs whose best patterns can warm-
    start a new search (ROADMAP: similarity-based measurement reuse)."""
    counts: Counter = Counter()
    for r in graph.regions:
        for k, v in r.feature_vector.items():
            counts[k] += v
        for name in r.callees:
            counts[f"call:{name.split('.')[-1]}"] += _CALL_WEIGHT
        counts[f"kind:{r.kind}"] += 1
    return dict(counts)
