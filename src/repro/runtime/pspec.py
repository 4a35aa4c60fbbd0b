"""Logical-axis sharding: maxtext-style rules mapping logical axis names to
mesh axes, with divisibility fallbacks.

Model code annotates activations with ``constrain(x, "batch", "seq", None)``;
outside a mesh context this is the identity, inside it becomes a
``with_sharding_constraint`` against the active rules.  Rules centralize the
DP/TP/EP/SP layout in one table (``runtime/sharding.py``) instead of
scattering mesh names through model code.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, tuple[str, ...]]

_STATE = threading.local()


class ShardingRules:
    """logical name -> mesh axis (or tuple of mesh axes)."""

    def __init__(self, mesh: Mesh, table: dict[str, AxisVal]):
        self.mesh = mesh
        self.table = dict(table)

    def resolve(self, logical: Optional[str], dim: int) -> AxisVal:
        """Resolve one logical axis to mesh axes, dropping non-divisible shards."""
        if logical is None:
            return None
        axes = self.table.get(logical)
        if axes is None:
            return None
        if isinstance(axes, str):
            axes = (axes,)
        # keep the longest prefix of mesh axes that divides the dim
        kept: list[str] = []
        size = 1
        for a in axes:
            if a not in self.mesh.shape:
                continue
            nxt = size * self.mesh.shape[a]
            if dim % nxt != 0:
                break
            kept.append(a)
            size = nxt
        if not kept:
            return None
        return tuple(kept) if len(kept) > 1 else kept[0]

    def pspec(self, shape: Sequence[int], logical_axes: Sequence[Optional[str]]) -> P:
        assert len(shape) == len(logical_axes), (shape, logical_axes)
        used: set[str] = set()
        parts: list[AxisVal] = []
        for dim, name in zip(shape, logical_axes):
            r = self.resolve(name, dim)
            # a mesh axis may appear at most once in a PartitionSpec
            if r is not None:
                rt = (r,) if isinstance(r, str) else r
                rt = tuple(a for a in rt if a not in used)
                used.update(rt)
                r = None if not rt else (rt[0] if len(rt) == 1 else rt)
            parts.append(r)
        return P(*parts)

    def sharding(self, shape: Sequence[int], logical_axes: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(self.mesh, self.pspec(shape, logical_axes))


@contextlib.contextmanager
def axis_rules(rules: Optional[ShardingRules]):
    prev = getattr(_STATE, "rules", None)
    _STATE.rules = rules
    try:
        yield rules
    finally:
        _STATE.rules = prev


def current_rules() -> Optional[ShardingRules]:
    return getattr(_STATE, "rules", None)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    rules = current_rules()
    if rules is None:
        return x
    spec = rules.pspec(x.shape, logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


# ---------------------------------------------------------------------------
# shard_map helpers: run a function with fully-local shards (used by the
# flash/wkv/rglru inner loops where SPMD propagation would thrash)
# ---------------------------------------------------------------------------


def dividing_axes(dim: int, candidates=(("pod", "data", "model"),
                                        ("data", "model"), ("pod", "data"),
                                        ("data",), ("model",))) -> tuple:
    """Longest mesh-axis tuple whose size divides `dim` (empty if none)."""
    rules = current_rules()
    if rules is None:
        return ()
    mesh = rules.mesh
    for cand in candidates:
        axes = tuple(a for a in cand if a in mesh.shape)
        if not axes:
            continue
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if dim % size == 0:
            return axes
    return ()


def local_map(fn, in_specs, out_specs, *args):
    """shard_map `fn` under the active rules' mesh (identity without rules).
    The body runs with rules disabled so nested `constrain`s are no-ops."""
    rules = current_rules()
    if rules is None:
        return fn(*args)

    def inner(*a):
        with axis_rules(None):
            return fn(*a)

    # replication checking is off: the local bodies intentionally compute
    # unreplicated partial results
    return jax.shard_map(inner, mesh=rules.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)
