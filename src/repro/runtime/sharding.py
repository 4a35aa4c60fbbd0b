"""Parameter / activation / decode-state sharding rules.

One table maps logical axis names to mesh axes (DP over ``pod``+``data``,
FSDP over ``data``, TP/EP/SP over ``model``); path-pattern rules assign
logical axes to every parameter and decode-state leaf.  Divisibility
fallbacks live in ``ShardingRules.resolve`` (non-divisible dims replicate).
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.runtime.pspec import ShardingRules

# ---------------------------------------------------------------------------
# logical axis -> mesh axes
# ---------------------------------------------------------------------------


def logical_table(mesh: Mesh) -> dict:
    has_pod = "pod" in mesh.shape
    return {
        "batch": ("pod", "data") if has_pod else ("data",),
        "fsdp": "data",
        "tensor": "model",
        "vocab": "model",
        "experts": "model",
        "seq_sp": "model",
        "kv_heads": "model",
        "kv_seq": "model",
    }


def make_rules(mesh: Mesh) -> ShardingRules:
    return ShardingRules(mesh, logical_table(mesh))


# ---------------------------------------------------------------------------
# parameter rules (matched on "/"-joined key path, right-aligned axes)
# ---------------------------------------------------------------------------

_P_IN_OUT = ("fsdp", "tensor")    # (d_in, d_out-parallel) weights
_P_OUT_IN = ("tensor", "fsdp")    # (d_in-parallel, d_out) weights

_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"moe/w_gate$", ("experts", "fsdp", None)),
    (r"moe/w_up$", ("experts", "fsdp", None)),
    (r"moe/w_down$", ("experts", None, "fsdp")),
    (r"moe/w_router$", ("fsdp", None)),
    (r"(^|/)(embed|lm_head)$", ("vocab", "fsdp")),
    (r"(^|/)(wq|wk|wv|wg|wr|w_gate|w_up|w_branch|w_in|dd_w1|w_lora_a|cm_wk|cm_wr|vis_w1)$",
     _P_IN_OUT),
    (r"(^|/)(wo|w_down|w_out|cm_wv|w_lora_b|dd_w2|vis_w2)$", _P_OUT_IN),
    (r"(^|/)(w_a|w_x)$", ("tensor", None, None)),
    (r"(^|/)w_conv$", (None, "tensor")),
    (r"(^|/)(lam|b_conv|b_a|b_x)$", ("tensor",)),
    (r"(^|/)w_router$", ("fsdp", None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


_ATTN_Q = re.compile(r"(attn|xattn)/(wq|wo|bq)$")
_ATTN_KV = re.compile(r"(attn|xattn)/(wk|wv|bk|bv)$")
_RWKV_HEADED = re.compile(r"tm_cm/(wr|wk|wv|wg|wo)$")


def _axes_for_param(path: str, ndim: int,
                    cfg: Optional[ArchConfig] = None,
                    mesh: Optional[Mesh] = None) -> tuple:
    # Attention projections: sharding the head dim over "model" only makes
    # sense when whole heads land on a device — otherwise the score einsums
    # contract over a sharded head_dim and XLA materializes giant gathers.
    if cfg is not None and mesh is not None:
        msize = mesh.shape.get("model", 1)
        if _ATTN_Q.search(path):
            ok = cfg.n_heads % msize == 0
            ax = ("tensor", "fsdp") if path.endswith("wo") else ("fsdp", "tensor")
            if not ok:
                ax = (None, "fsdp") if path.endswith("wo") else ("fsdp", None)
            return (None,) * (ndim - len(ax)) + ax[-ndim:]
        if _ATTN_KV.search(path):
            ok = cfg.n_kv_heads % msize == 0
            ax = ("fsdp", "tensor") if ok else ("fsdp", None)
            return (None,) * (ndim - len(ax)) + ax[-ndim:]
        if _RWKV_HEADED.search(path):
            nh = cfg.d_model // max(cfg.rwkv_head_dim, 1)
            ok = nh % msize == 0
            if path.endswith("wo"):
                ax = ("tensor", "fsdp") if ok else (None, "fsdp")
            else:
                ax = ("fsdp", "tensor") if ok else ("fsdp", None)
            return (None,) * (ndim - len(ax)) + ax
    for pat, axes in _PARAM_RULES:
        if re.search(pat, path):
            if len(axes) > ndim:
                axes = axes[-ndim:]
            return (None,) * (ndim - len(axes)) + tuple(axes)
    return (None,) * ndim


def param_logical_axes(param_shapes: Any, cfg: Optional[ArchConfig] = None,
                       mesh: Optional[Mesh] = None) -> Any:
    """Pytree of logical-axis tuples matching the params structure."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _axes_for_param(_path_str(path), len(leaf.shape),
                                           cfg, mesh),
        param_shapes)


def tree_pspecs(rules: ShardingRules, shapes: Any, axes: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda leaf, ax: rules.pspec(leaf.shape, ax), shapes, axes)


def tree_shardings(rules: ShardingRules, shapes: Any, axes: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda leaf, ax: NamedSharding(rules.mesh, rules.pspec(leaf.shape, ax)),
        shapes, axes)


# ---------------------------------------------------------------------------
# decode-state rules
# ---------------------------------------------------------------------------


def _axes_for_state(path: str, shape: tuple, cfg: ArchConfig, mesh: Mesh) -> tuple:
    ndim = len(shape)
    model = mesh.shape.get("model", 1)
    if path.endswith("cache_len"):
        return ()
    if re.search(r"(^|/)(k|v|xk|xv)$", path):
        # head-major (L, B, Hkv, S, D) or (B, Hkv, S, D)
        hkv, s = shape[-3], shape[-2]
        lead = (None,) * (ndim - 4)
        if hkv % model == 0:
            return lead + ("batch", "kv_heads", None, None)
        if s % model == 0:
            return lead + ("batch", None, "kv_seq", None)
        return lead + ("batch", None, None, None)
    if path.endswith("wkv"):  # (L,B,H,Dk,Dv)
        h = shape[-3]
        lead = (None,) * (ndim - 4)
        if h % model == 0:
            return lead + ("batch", "kv_heads", None, None)
        return lead + ("batch", None, None, "tensor")
    if re.search(r"shift_(tm|cm)$", path):  # (L,B,d)
        return (None,) * (ndim - 2) + ("batch", "tensor")
    if path.endswith("/h"):  # rglru state (L,B,dr)
        return (None,) * (ndim - 2) + ("batch", "tensor")
    if path.endswith("conv"):  # (L,B,w-1,dr)
        return (None,) * (ndim - 3) + ("batch", None, "tensor")
    return (None,) * ndim


def state_logical_axes(state_shapes: Any, cfg: ArchConfig, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _axes_for_state(_path_str(path), tuple(leaf.shape), cfg, mesh),
        state_shapes)


# ---------------------------------------------------------------------------
# batch (input) rules
# ---------------------------------------------------------------------------


def batch_logical_axes(batch_shapes: Any) -> Any:
    def f(path, leaf):
        ndim = len(leaf.shape)
        return ("batch",) + (None,) * (ndim - 1)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: f(path, leaf), batch_shapes)
