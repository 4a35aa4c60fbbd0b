"""Mixture-of-Experts block (olmoe 64e/top-8, llama4-scout 16e/top-1 + shared).

Two region implementations (ExecPlan.moe_impl):

* ``dense_onehot`` — reference: every token runs through every expert, the
  top-k one-hot gate zeroes the rest.  Numerically equals the dispatched
  path with infinite capacity; E-times the FLOPs (the "CPU path").
* ``scatter_ep``   — production: top-k routing, capacity-limited scatter into
  per-expert (E, C, d) buffers, batched expert matmuls, weighted combine.
  Expert dim shards over the "model"/"expert" mesh axis (EP).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import numpy as np
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.plan import ExecPlan

Array = jax.Array


class MoEAux(NamedTuple):
    load_balance: Array  # scalar
    router_z: Array      # scalar


def moe_init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    e = cfg.moe
    d, ff = cfg.d_model, (e.d_ff_expert or cfg.d_ff)
    ks = jax.random.split(key, 5)
    p = {
        "w_router": L.dense_init(ks[0], (d, e.n_experts), dtype=jnp.float32),
        "w_gate": L.dense_init(ks[1], (e.n_experts, d, ff), dtype=dtype),
        "w_up": L.dense_init(ks[2], (e.n_experts, d, ff), dtype=dtype),
        "w_down": L.dense_init(ks[3], (e.n_experts, ff, d), in_axis=-2, dtype=dtype),
    }
    if e.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], d, ff * e.n_shared_experts, dtype=dtype)
    return p


def _route(x2d: Array, p: dict, cfg: ArchConfig) -> tuple[Array, Array, MoEAux]:
    """Router: returns (gates (T,k), expert idx (T,k), aux losses)."""
    e = cfg.moe
    logits = (x2d.astype(jnp.float32) @ p["w_router"])  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, e.top_k)  # (T,k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + z-loss
    density = jnp.mean(jax.nn.one_hot(idx, e.n_experts), axis=(0, 1))  # (E,)
    density_prob = jnp.mean(probs, axis=0)
    lb = e.n_experts * jnp.sum(density * density_prob)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return gates, idx, MoEAux(lb, z)


# ---------------------------------------------------------------------------
# reference: dense one-hot
# ---------------------------------------------------------------------------


def moe_dense(x2d: Array, p: dict, cfg: ArchConfig, plan: ExecPlan) -> tuple[Array, MoEAux]:
    e = cfg.moe
    dt = L.cdtype(plan)
    gates, idx, aux = _route(x2d, p, cfg)
    # (T, E) combined gate matrix (zero outside top-k)
    onehot = jax.nn.one_hot(idx, e.n_experts, dtype=jnp.float32)  # (T,k,E)
    combine = jnp.einsum("tk,tke->te", gates, onehot).astype(dt)
    # every token through every expert
    g = jnp.einsum("td,edf->tef", x2d, p["w_gate"].astype(dt))
    u = jnp.einsum("td,edf->tef", x2d, p["w_up"].astype(dt))
    h = L._act(g, cfg.mlp_act if cfg.mlp_act != "relu_sq" else "silu") * u
    y = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(dt))
    out = jnp.einsum("ted,te->td", y, combine)
    return out + _shared(x2d, p, cfg, plan), aux


# ---------------------------------------------------------------------------
# production: capacity-limited scatter dispatch (EP)
# ---------------------------------------------------------------------------


def moe_scatter(x2d: Array, p: dict, cfg: ArchConfig, plan: ExecPlan) -> tuple[Array, MoEAux]:
    e = cfg.moe
    dt = L.cdtype(plan)
    t, d = x2d.shape
    gates, idx, aux = _route(x2d, p, cfg)

    n = t * e.top_k
    cap = int(max(1, (t * e.top_k / e.n_experts) * e.capacity_factor))
    e_flat = idx.reshape(-1)                         # (N,)
    tok_flat = jnp.repeat(jnp.arange(t), e.top_k)    # (N,)
    gate_flat = gates.reshape(-1)

    # within-expert rank via sort (dropless up to capacity)
    order = jnp.argsort(e_flat)
    sorted_e = e_flat[order]
    counts = jnp.bincount(e_flat, length=e.n_experts)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(n) - starts[sorted_e]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

    keep = rank < cap

    # 2-D scatter into (E, C, d); out-of-capacity rows drop (token dropping).
    xb = jnp.zeros((e.n_experts, cap, d), dt)
    xb = xb.at[e_flat, rank].set(x2d[tok_flat].astype(dt), mode="drop")
    xb = pspec_constrain_experts(xb)

    # batched expert FFN: (E, C, d) x (E, d, ff)
    g = jnp.einsum("ecd,edf->ecf", xb, p["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xb, p["w_up"].astype(dt))
    h = L._act(g, cfg.mlp_act if cfg.mlp_act != "relu_sq" else "silu") * u
    yb = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))
    yb = pspec_constrain_experts(yb)

    # combine: gather back and weight
    rank_c = jnp.clip(rank, 0, cap - 1)
    gathered = jnp.where(keep[:, None], yb[e_flat, rank_c], 0.0)
    weighted = gathered * gate_flat[:, None].astype(dt)
    out = jnp.zeros((t, d), dt).at[tok_flat].add(weighted)
    return out + _shared(x2d, p, cfg, plan), aux


def pspec_constrain_experts(xb: Array) -> Array:
    from repro.runtime.pspec import constrain
    return constrain(xb, "experts", None, None)


def _shared(x2d: Array, p: dict, cfg: ArchConfig, plan: ExecPlan) -> Array:
    if "shared" not in p:
        return jnp.zeros((), L.cdtype(plan))
    return L.mlp(x2d, p["shared"], cfg.mlp_act if cfg.mlp_act != "relu_sq" else "silu", plan)


# ---------------------------------------------------------------------------
# shard_map EP: per-shard local dispatch + all_to_all over the expert axis.
# Tokens shard over the whole mesh; each shard routes its own tokens into
# (E, C_loc, d) buffers, all_to_all swaps expert-major <-> shard-major,
# local experts run batched matmuls, all_to_all returns, combine locally.
# FSDP'd expert weights are all-gathered explicitly inside (the per-layer
# gather — the paper's transfer-hoisting knob, made explicit).
# ---------------------------------------------------------------------------


def _moe_ep_body(x_loc, wr, wg, wu, wd, *, cfg: ArchConfig, plan: ExecPlan,
                 t_axes: tuple, msize: int):
    e = cfg.moe
    dt = L.cdtype(plan)
    tl, d = x_loc.shape
    # FSDP gathers (weights enter sharded over "data" on their d/ff dims)
    wr = jax.lax.all_gather(wr, "data", axis=0, tiled=True)     # (d, E)
    wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)     # (E_loc, d, ff)
    wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
    wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)     # (E_loc, ff, d)

    logits = x_loc.astype(jnp.float32) @ wr                      # (Tl, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, e.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    n = tl * e.top_k
    cap = int(max(1, (tl * e.top_k / e.n_experts) * e.capacity_factor))
    e_flat = idx.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(tl), e.top_k)
    gate_flat = gates.reshape(-1)

    order = jnp.argsort(e_flat)
    sorted_e = e_flat[order]
    counts = jnp.bincount(e_flat, length=e.n_experts)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(n) - starts[sorted_e]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < cap

    buf = jnp.zeros((e.n_experts, cap, d), dt)
    buf = buf.at[e_flat, rank].set(x_loc[tok_flat].astype(dt), mode="drop")

    # expert-major <-> shard-major swap (EP all_to_all over "model")
    xb = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                            tiled=True)                          # (E_loc, m*C, d)
    g = jnp.einsum("ecd,edf->ecf", xb, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xb, wu.astype(dt))
    h = L._act(g, cfg.mlp_act if cfg.mlp_act != "relu_sq" else "silu") * u
    yb = jnp.einsum("ecf,efd->ecd", h, wd.astype(dt))
    yb = jax.lax.all_to_all(yb, "model", split_axis=1, concat_axis=0,
                            tiled=True)                          # (E, C, d)

    rank_c = jnp.clip(rank, 0, cap - 1)
    gathered = jnp.where(keep[:, None], yb[e_flat, rank_c], 0.0)
    y = jnp.zeros((tl, d), dt).at[tok_flat].add(
        gathered * gate_flat[:, None].astype(dt))

    # aux losses (global means via pmean over every token axis)
    density = jnp.mean(jax.nn.one_hot(idx, e.n_experts), axis=(0, 1))
    density_prob = jnp.mean(probs, axis=0)
    lb = e.n_experts * jnp.sum(
        jax.lax.pmean(density, t_axes) * jax.lax.pmean(density_prob, t_axes))
    z = jax.lax.pmean(
        jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))), t_axes)
    return y, lb, z


def moe_scatter_ep_sharded(x2d: Array, p: dict, cfg: ArchConfig,
                           plan: ExecPlan) -> Optional[tuple[Array, MoEAux]]:
    """shard_map EP path; returns None when the mesh doesn't apply."""
    from jax.sharding import PartitionSpec as P
    from repro.runtime.pspec import current_rules, dividing_axes, axis_rules

    rules = current_rules()
    if rules is None:
        return None
    mesh = rules.mesh
    msize = mesh.shape.get("model", 1)
    if msize <= 1 or "data" not in mesh.shape:
        return None
    if cfg.moe.n_experts % msize != 0:
        return None
    t = x2d.shape[0]
    t_axes = dividing_axes(t, (("pod", "data", "model"), ("data", "model")))
    if "model" not in t_axes:
        return None
    tl = t // int(np.prod([mesh.shape[a] for a in t_axes]))
    if tl < cfg.moe.n_experts:  # degenerate local dispatch
        return None

    import functools
    body = functools.partial(_moe_ep_body, cfg=cfg, plan=plan,
                             t_axes=t_axes, msize=msize)

    def inner(x_loc, wr, wg, wu, wd):
        with axis_rules(None):
            return body(x_loc, wr, wg, wu, wd)

    tspec = P(t_axes, None)
    y, lb, z = jax.shard_map(
        inner, mesh=mesh, check_vma=False,
        in_specs=(tspec, P("data", None), P("model", "data", None),
                  P("model", "data", None), P("model", None, "data")),
        out_specs=(tspec, P(), P()),
    )(x2d, p["w_router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, MoEAux(lb, z)


def moe_block(x: Array, p: dict, cfg: ArchConfig, plan: ExecPlan) -> tuple[Array, MoEAux]:
    """x: (B,S,d) -> (B,S,d), aux."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    if plan.moe_impl == "scatter_ep":
        out = moe_scatter_ep_sharded(x2d, p, cfg, plan)
        if out is not None:
            y, aux = out
            y = y + _shared(x2d, p, cfg, plan)
        else:
            y, aux = moe_scatter(x2d, p, cfg, plan)
    else:
        y, aux = moe_dense(x2d, p, cfg, plan)
    return y.reshape(b, s, d), aux
