"""Production meshes.

Single pod: 16x16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the ``pod`` axis
carries pure DP (gradient all-reduce only — the slow DCN/ICI hop that the
compression path targets).

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: sharding propagates from
    the shardings on the arguments and the ``constrain`` hints, as the model
    code expects (``make_mesh`` otherwise defaults to explicit axes, under
    which a gather such as ``jnp.take`` must name its output sharding)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """Whatever this host actually has (tests / examples)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    return auto_mesh((n // model, model), ("data", "model"))


@functools.lru_cache(maxsize=None)
def make_destination_mesh(n: int, axis: str = "data"):
    """The mesh behind one ``MeshDestination`` gene: ``n`` devices on a
    single named axis.  Cached per (n, axis) — the device set is fixed for
    the process, and the substitution engine asks for the same mesh once
    per sharded site."""
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(f"mesh destination wants {n} devices, "
                         f"host has {len(devices)}")
    return auto_mesh((n,), (axis,))
