"""Unified decoder-only LM covering dense / MoE / hybrid(RG-LRU) / SSM(RWKV6)
/ VLM families, with scan-over-layers stacked parameters.

Three entry points per model (built by ``models/api.py``):
  * ``loss``    — training forward + masked cross-entropy (+ MoE aux)
  * ``prefill`` — full-sequence forward returning logits + decode state
  * ``decode``  — one-token step against the decode state

The decode state is a plain nested dict of arrays (stacked per-layer leaves)
so it shards/specs like any pytree.  Implementation choices come from the
ExecPlan (the paper's offload genes).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import rwkv as W
from repro.models.plan import ExecPlan
from repro.runtime.pspec import constrain

Array = jax.Array


# ---------------------------------------------------------------------------
# remat policy
# ---------------------------------------------------------------------------


def _maybe_remat(fn, plan: ExecPlan):
    if plan.remat == "none":
        return fn
    if plan.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # "full": save nothing


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def _stack_init(key, n: int, init_fn) -> Any:
    """Initialize n copies of a param dict and stack leaves on axis 0."""
    keys = jax.random.split(key, n)
    trees = [init_fn(k) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _dense_block_init(key, cfg: ArchConfig, dtype, moe: bool = True) -> dict:
    k1, k2 = jax.random.split(key)
    attn_init = A.mla_init if cfg.attn_kind == "mla" else A.attn_init
    blk = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": attn_init(k1, cfg, dtype=dtype),
    }
    if cfg.moe is not None and moe:
        blk["moe"] = M.moe_init(k2, cfg, dtype=dtype)
    else:
        blk["mlp"] = L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype)
    return blk


def _hybrid_sub_init(key, cfg: ArchConfig, kind: str, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    sub = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype),
    }
    if kind == "rglru":
        sub["rglru"] = R.rglru_init(k1, cfg, dtype=dtype)
    else:
        sub["attn"] = A.attn_init(k1, cfg, dtype=dtype)
    return sub


def _hybrid_macro_init(key, cfg: ArchConfig, dtype) -> dict:
    ks = jax.random.split(key, len(cfg.block_pattern))
    return {f"sub{i}": _hybrid_sub_init(ks[i], cfg, kind, dtype)
            for i, kind in enumerate(cfg.block_pattern)}


def _rwkv_block_init(key, cfg: ArchConfig, dtype) -> dict:
    return {
        "ln1_s": jnp.ones((cfg.d_model,), dtype),
        "ln1_b": jnp.zeros((cfg.d_model,), dtype),
        "ln2_s": jnp.ones((cfg.d_model,), dtype),
        "ln2_b": jnp.zeros((cfg.d_model,), dtype),
        "tm_cm": W.rwkv_init(key, cfg, dtype=dtype),
    }


def init_params(cfg: ArchConfig, rng: jax.Array, dtype=jnp.float32) -> dict:
    k_embed, k_blocks, k_head, k_extra = jax.random.split(rng, 4)
    params: dict = {"embed": L.embed_init(k_embed, (cfg.vocab, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = L.embed_init(k_head, (cfg.vocab, cfg.d_model), dtype)
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype)

    if cfg.family == "ssm":
        params["embed_norm_s"] = jnp.ones((cfg.d_model,), dtype)
        params["embed_norm_b"] = jnp.zeros((cfg.d_model,), dtype)
        params["blocks"] = _stack_init(
            k_blocks, cfg.n_layers, lambda k: _rwkv_block_init(k, cfg, dtype))
    elif cfg.family == "hybrid":
        period = len(cfg.block_pattern)
        n_macro, rem = divmod(cfg.n_layers, period)
        kp, km = jax.random.split(k_blocks)
        if rem:
            pre_ks = jax.random.split(kp, rem)
            params["pre_blocks"] = [
                _hybrid_sub_init(pre_ks[i], cfg, "rglru", dtype) for i in range(rem)]
        params["blocks"] = _stack_init(
            km, n_macro, lambda k: _hybrid_macro_init(k, cfg, dtype))
    else:  # dense / moe / vlm trunk
        if cfg.n_dense_layers:  # leading dense layers, outside the scan
            kd, k_blocks = jax.random.split(k_blocks)
            params["dense_blocks"] = _stack_init(
                kd, cfg.n_dense_layers,
                lambda k: _dense_block_init(k, cfg, dtype, moe=False))
        params["blocks"] = _stack_init(
            k_blocks, cfg.n_layers - cfg.n_dense_layers,
            lambda k: _dense_block_init(k, cfg, dtype))

    if cfg.vision_patches:
        kv1, kv2 = jax.random.split(k_extra)
        params["projector"] = {
            "vis_w1": L.dense_init(kv1, (cfg.vision_dim, cfg.d_model), dtype=dtype),
            "vis_b1": jnp.zeros((cfg.d_model,), dtype),
            "vis_w2": L.dense_init(kv2, (cfg.d_model, cfg.d_model), dtype=dtype),
            "vis_b2": jnp.zeros((cfg.d_model,), dtype),
        }
    return params


# ---------------------------------------------------------------------------
# block forward — full-sequence mode (train / prefill)
# ---------------------------------------------------------------------------


def _attn_sublayer_full(x, p_attn, ln, cfg: ArchConfig, plan: ExecPlan,
                        positions, want_cache: bool, cache_capacity: int):
    b, s, _ = x.shape
    h = L.rmsnorm(x, ln, cfg.norm_eps, plan)
    if cfg.attn_kind == "mla":
        return _mla_sublayer_full(x, h, p_attn, cfg, plan, positions,
                                  want_cache, cache_capacity)
    with jax.named_scope("attention"):
        q, k, v = A.project_qkv(h, p_attn, cfg, plan, positions)
        o = A.attend(q, k, v, positions, positions, causal=True,
                     attn_kind=cfg.attn_kind, window=cfg.local_window,
                     plan=plan)
        o = o.reshape(b, s, -1) @ p_attn["wo"].astype(L.cdtype(plan))
        o = constrain(o, "batch", "seq", None)
    cache = None
    if want_cache:
        with jax.named_scope("kv_cache"):
            cache = _prefill_cache(k, v, cfg, s, cache_capacity)
    return x + o, cache


def _mla_sublayer_full(x, h, p_attn, cfg: ArchConfig, plan: ExecPlan,
                       positions, want_cache: bool, cache_capacity: int):
    """Latent attention over the sequence; its cache is the normalised
    latent and the rotated shared key, (B, capacity, R) and (B, capacity,
    dr), zero past the prompt."""
    with jax.named_scope("attention"):
        o, c, k_pe = A.mla_prefill(h, p_attn, cfg, plan, positions)
        o = constrain(o @ p_attn["wo"].astype(L.cdtype(plan)),
                      "batch", "seq", None)
    cache = None
    if want_cache:
        with jax.named_scope("kv_cache"):
            pad = ((0, 0), (0, cache_capacity - x.shape[1]), (0, 0))
            cache = {"c": jnp.pad(c, pad), "k_pe": jnp.pad(k_pe, pad)}
    return x + o, cache


def _prefill_cache(k, v, cfg: ArchConfig, s: int, cache_capacity: int):
    """The head-major decode cache a prefill of ``s`` tokens leaves: the
    last window as a ring (local attention), else k/v padded to
    ``cache_capacity``."""
    if cfg.attn_kind == "local":
        w = cfg.local_window
        if s >= w:  # ring layout: slot = position % window
            k, v = (jnp.roll(x[:, -w:], (s % w) - w, axis=1) for x in (k, v))
        return {"k": A.to_cache(k, w), "v": A.to_cache(v, w)}
    return {"k": A.to_cache(k, cache_capacity),
            "v": A.to_cache(v, cache_capacity)}


def _mlp_sublayer_full(x, blk, cfg: ArchConfig, plan: ExecPlan):
    h = L.rmsnorm(x, blk["ln2"], cfg.norm_eps, plan)
    if "moe" in blk:
        y, aux = M.moe_block(h, blk["moe"], cfg, plan)
        aux_vec = jnp.stack([aux.load_balance, aux.router_z])
    else:
        y = L.mlp(h, blk["mlp"], cfg.mlp_act, plan)
        aux_vec = jnp.zeros((2,), jnp.float32)
    y = constrain(y, "batch", "seq", None)
    return x + y, aux_vec


def _dense_block_full(x, blk, cfg, plan, positions, want_cache, cache_capacity):
    x, cache = _attn_sublayer_full(
        x, blk["attn"], blk["ln1"], cfg, plan, positions, want_cache, cache_capacity)
    x, aux = _mlp_sublayer_full(x, blk, cfg, plan)
    return x, aux, cache


def _rglru_sublayer_full(x, sub, cfg, plan, state=None):
    h = L.rmsnorm(x, sub["ln1"], cfg.norm_eps, plan)
    y, new_state = R.rglru_block(h, sub["rglru"], cfg, plan, state)
    x = x + constrain(y, "batch", "seq", None)
    h2 = L.rmsnorm(x, sub["ln2"], cfg.norm_eps, plan)
    x = x + L.mlp(h2, sub["mlp"], cfg.mlp_act, plan)
    return x, new_state


def _hybrid_macro_full(x, blk, cfg, plan, positions, want_cache):
    states: dict = {}
    cache = None
    for i, kind in enumerate(cfg.block_pattern):
        sub = blk[f"sub{i}"]
        if kind == "rglru":
            x, st = _rglru_sublayer_full(x, sub, cfg, plan)
            states[f"rglru{i}"] = {"h": st.h, "conv": st.conv}
        else:
            x, kv = _attn_sublayer_full(
                x, sub["attn"], sub["ln1"], cfg, plan, positions,
                want_cache, cfg.local_window)
            x, _ = _mlp_sublayer_full(x, sub, cfg, plan)
            if want_cache:
                cache = kv
    if not want_cache:
        states = {k: None for k in states}
    return x, states, cache


def _rwkv_block_full(x, blk, cfg, plan, state=None):
    p = blk["tm_cm"]
    h = L.layernorm(x, blk["ln1_s"], blk["ln1_b"], cfg.norm_eps)
    prev = W.RWKVState(state["wkv"], state["shift_tm"], state["shift_cm"]) if state else None
    y, wkv, last_tm = W.time_mix(h, p, cfg, plan, prev)
    x = x + constrain(y, "batch", "seq", None)
    h2 = L.layernorm(x, blk["ln2_s"], blk["ln2_b"], cfg.norm_eps)
    y2, last_cm = W.channel_mix(h2, p, cfg, plan, prev)
    x = x + y2
    return x, {"wkv": wkv, "shift_tm": last_tm, "shift_cm": last_cm}


# ---------------------------------------------------------------------------
# trunk forward (full-sequence)
# ---------------------------------------------------------------------------


def _cast_blocks(blocks, plan: ExecPlan):
    """Optionally cast float weights to the compute dtype BEFORE the layer
    scan, so per-layer FSDP all-gathers move bf16 instead of fp32 (halves
    the dominant collective term; grads still accumulate into fp32 masters
    through the differentiable cast)."""
    if plan.gather_dtype != "compute":
        return blocks
    dt = L.cdtype(plan)
    return jax.tree_util.tree_map(
        lambda w: w.astype(dt) if jnp.issubdtype(w.dtype, jnp.floating) else w,
        blocks)


def forward_full(params: dict, x: Array, cfg: ArchConfig, plan: ExecPlan,
                 positions: Array, want_cache: bool = False,
                 cache_capacity: int = 0) -> tuple[Array, Array, dict]:
    """x: (B,S,d) embedded inputs.  Returns (hidden, aux(2,), decode_caches)."""
    caches: dict = {}
    cache_capacity = cache_capacity or x.shape[1]
    params = dict(params)
    params["blocks"] = _cast_blocks(params["blocks"], plan)

    if cfg.family == "ssm":
        def body(carry, blk):
            h, st = _rwkv_block_full(carry, blk, cfg, plan)
            outs = st if want_cache else jnp.zeros((), jnp.float32)
            return h, outs
        body = _maybe_remat(body, plan)
        x, sts = jax.lax.scan(body, x, params["blocks"])
        if want_cache:
            caches["rwkv"] = sts
        return x, jnp.zeros((2,), jnp.float32), caches

    if cfg.family == "hybrid":
        pre_states = []
        for sub in params.get("pre_blocks", []):
            x, st = _rglru_sublayer_full(x, sub, cfg, plan)
            pre_states.append({"h": st.h, "conv": st.conv})

        def body(carry, blk):
            h, states, kv = _hybrid_macro_full(carry, blk, cfg, plan, positions, want_cache)
            outs = (states, kv) if want_cache else jnp.zeros((), jnp.float32)
            return h, outs
        body = _maybe_remat(body, plan)
        x, outs = jax.lax.scan(body, x, params["blocks"])
        if want_cache:
            states, kv = outs
            caches["macro_rglru"] = states
            caches["macro_kv"] = kv
            if pre_states:
                caches["pre_rglru"] = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *pre_states)
        return x, jnp.zeros((2,), jnp.float32), caches

    # dense / moe / vlm; leading dense layers first, unrolled
    pre_kv = []
    for i in range(cfg.n_dense_layers):
        blk = jax.tree_util.tree_map(lambda a: a[i], params["dense_blocks"])
        x, _, kv = _dense_block_full(x, blk, cfg, plan, positions, want_cache,
                                     cache_capacity)
        pre_kv.append(kv)
    if want_cache and pre_kv:
        caches["pre_kv"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *pre_kv)

    def body(carry, blk):
        h, aux, kv = _dense_block_full(
            carry, blk, cfg, plan, positions, want_cache, cache_capacity)
        outs = (aux, kv) if want_cache else aux
        return h, outs
    body = _maybe_remat(body, plan)
    x, outs = jax.lax.scan(body, x, params["blocks"])
    if want_cache:
        auxs, kv = outs
        caches["kv"] = kv
    else:
        auxs = outs
    return x, jnp.sum(auxs, axis=0), caches


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------


def embed_inputs(params: dict, cfg: ArchConfig, plan: ExecPlan, tokens: Array,
                 patch_feats: Optional[Array] = None) -> Array:
    with jax.named_scope("embed"):
        x = L.embed_tokens(tokens, params["embed"], plan,
                           cfg.scale_embeddings)
        if cfg.vision_patches and patch_feats is not None:
            pj = params["projector"]
            dt = L.cdtype(plan)
            v = jax.nn.gelu(patch_feats.astype(dt) @ pj["vis_w1"].astype(dt)
                            + pj["vis_b1"].astype(dt), approximate=True)
            v = v @ pj["vis_w2"].astype(dt) + pj["vis_b2"].astype(dt)
            x = jnp.concatenate([v, x], axis=1)
        if cfg.family == "ssm":
            x = L.layernorm(x, params["embed_norm_s"], params["embed_norm_b"],
                            cfg.norm_eps)
        return constrain(x, "batch", "seq", None)


def head_table(params: dict) -> Array:
    return params["embed"] if "lm_head" not in params else params["lm_head"]


def lm_logits(params: dict, cfg: ArchConfig, plan: ExecPlan, hidden: Array) -> Array:
    with jax.named_scope("head"):
        h = L.rmsnorm(hidden, params["final_norm"], cfg.norm_eps, plan)
        out = L.logits_from_hidden(h, head_table(params), plan,
                                   cfg.logit_softcap)
        return constrain(out, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# loss (train step core)
# ---------------------------------------------------------------------------


def lm_loss(params: dict, batch: dict, cfg: ArchConfig, plan: ExecPlan) -> tuple[Array, dict]:
    tokens = batch["tokens"]
    labels = batch["labels"]
    patch = batch.get("patch_feats")
    frames = batch.get("frames")  # only whisper (handled in whisper.py)
    del frames
    x = embed_inputs(params, cfg, plan, tokens, patch)
    s_total = x.shape[1]
    positions = jnp.arange(s_total, dtype=jnp.int32)
    hidden, aux, _ = forward_full(params, x, cfg, plan, positions)
    # labels align with the token part (vlm: image prefix carries no loss)
    hidden = hidden[:, s_total - tokens.shape[1]:]
    hidden = L.rmsnorm(hidden, params["final_norm"], cfg.norm_eps, plan)
    mask = (labels >= 0).astype(jnp.float32)
    safe_labels = jnp.maximum(labels, 0)
    if plan.loss_impl == "chunked_vocab":
        nll = L.cross_entropy_chunked(hidden, head_table(params), safe_labels,
                                      plan, cfg.logit_softcap)
    else:
        logits = L.logits_from_hidden(hidden, head_table(params), plan, cfg.logit_softcap)
        logits = constrain(logits, "batch", "seq", "vocab")
        nll = L.cross_entropy_full(logits, safe_labels)
    ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    metrics = {"ce": ce}
    loss = ce
    if cfg.moe is not None:
        lb, z = aux[0] / cfg.n_layers, aux[1] / cfg.n_layers
        loss = loss + cfg.moe.aux_loss * lb + cfg.moe.router_z_loss * z
        metrics.update({"moe_lb": lb, "moe_z": z})
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------


def prefill(params: dict, cfg: ArchConfig, plan: ExecPlan, tokens: Array,
            patch_feats: Optional[Array] = None,
            cache_capacity: int = 0) -> tuple[Array, dict]:
    """Returns (last-token logits, decode state)."""
    x = embed_inputs(params, cfg, plan, tokens, patch_feats)
    s_total = x.shape[1]
    positions = jnp.arange(s_total, dtype=jnp.int32)
    hidden, _, caches = forward_full(
        params, x, cfg, plan, positions, want_cache=True,
        cache_capacity=max(cache_capacity, s_total))
    logits = lm_logits(params, cfg, plan, hidden[:, -1:])
    state = dict(caches)
    state["cache_len"] = jnp.asarray(s_total, jnp.int32)
    return logits, state


def _dense_block_decode(x1, blk, kv, cache_len, cfg, plan):
    """One layer's step against its cache ``kv`` (head-major, read only).
    Returns the new hidden state and the token's own k/v, (B,Hkv,1,D)
    (latent attention: its c (B,1,R) and k_pe (B,1,dr))."""
    h = L.rmsnorm(x1, blk["ln1"], cfg.norm_eps, plan)
    if cfg.attn_kind == "mla":
        with jax.named_scope("attention"):
            pos = cache_len[None].astype(jnp.int32)
            valid = A.decode_valid(kv["c"].shape[1], cache_len, False)
            o, c, k_pe = A.mla_decode(h, blk["attn"], cfg, plan, kv["c"],
                                      kv["k_pe"], valid, pos)
            o = o @ blk["attn"]["wo"].astype(L.cdtype(plan))
        x1, _ = _mlp_sublayer_full(x1 + o, blk, cfg, plan)
        return x1, {"c": c, "k_pe": k_pe}
    with jax.named_scope("attention"):
        pos = cache_len[None].astype(jnp.int32)
        q, k, v = A.project_qkv(h, blk["attn"], cfg, plan, pos)
        valid = A.decode_valid(kv["k"].shape[2], cache_len,
                               cfg.attn_kind == "local")
        o = A.attend_decode(q, A.KVCache(kv["k"], kv["v"]), valid, plan, k, v)
        o = o.reshape(x1.shape[0], 1, -1) \
            @ blk["attn"]["wo"].astype(L.cdtype(plan))
    x1 = x1 + o
    x1, _ = _mlp_sublayer_full(x1, blk, cfg, plan)
    return x1, {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3)}


def _rglru_sublayer_decode(x1, sub, st, cfg, plan):
    state = R.RGLRUState(st["h"], st["conv"])
    h = L.rmsnorm(x1, sub["ln1"], cfg.norm_eps, plan)
    y, new_state = R.rglru_block(h, sub["rglru"], cfg, plan, state)
    x1 = x1 + y
    h2 = L.rmsnorm(x1, sub["ln2"], cfg.norm_eps, plan)
    x1 = x1 + L.mlp(h2, sub["mlp"], cfg.mlp_act, plan)
    return x1, {"h": new_state.h, "conv": new_state.conv}


def _tree_index(tree, i):
    with jax.named_scope("kv_cache"):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tree)


def _tree_update(tree, sub, i):
    with jax.named_scope("kv_cache"):
        return jax.tree_util.tree_map(
            lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s, i, 0),
            tree, sub)


def decode_step(params: dict, cfg: ArchConfig, plan: ExecPlan, token: Array,
                state: dict) -> tuple[Array, dict]:
    """token: (B,1) int32.  Returns (logits (B,1,V), new state).

    The stacked KV caches enter the layer scan as read-only ``xs``: each
    layer's attention reads its cache once, in place.  The new token's k/v
    leave the scan as token-sized ``ys``, and one ``A.write_tokens`` after
    the scan puts them in their slot of the donated stack, so one
    cache-sized buffer is live and a step writes only the token.  The small
    recurrent states (RG-LRU, RWKV), replaced whole every step, travel as
    carries indexed and written back per layer.
    """
    cache_len = state["cache_len"]
    ring = cfg.attn_kind == "local"
    x1 = embed_inputs(params, cfg, plan, token, None)
    new_state: dict = {"cache_len": cache_len + 1}

    if cfg.family == "ssm":
        n_layers = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]

        def body(carry, blk_i):
            h, caches = carry
            blk, i = blk_i
            st = _tree_index(caches, i)
            h, new_st = _rwkv_block_full(h, blk, cfg, plan, state=st)
            return (h, _tree_update(caches, new_st, i)), None
        (x1, sts), _ = jax.lax.scan(
            body, (x1, state["rwkv"]),
            (params["blocks"], jnp.arange(n_layers)))
        new_state["rwkv"] = sts
    elif cfg.family == "hybrid":
        pre_states = []
        for i, sub in enumerate(params.get("pre_blocks", [])):
            st = jax.tree_util.tree_map(lambda a: a[i], state["pre_rglru"])
            x1, new_st = _rglru_sublayer_decode(x1, sub, st, cfg, plan)
            pre_states.append(new_st)
        if pre_states:
            new_state["pre_rglru"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *pre_states)

        n_macro = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]

        def body(carry, xs):
            h, rg_all = carry
            blk, i, kv = xs
            rg_st = _tree_index(rg_all, i)
            new_rg: dict = {}
            new_kv = None
            for j, kind in enumerate(cfg.block_pattern):
                sub = blk[f"sub{j}"]
                if kind == "rglru":
                    h, new_rg[f"rglru{j}"] = _rglru_sublayer_decode(
                        h, sub, rg_st[f"rglru{j}"], cfg, plan)
                else:
                    h, new_kv = _dense_block_decode(h, sub, kv, cache_len, cfg, plan)
            return (h, _tree_update(rg_all, new_rg, i)), new_kv
        (x1, rg_sts), new_kv = jax.lax.scan(
            body, (x1, state["macro_rglru"]),
            (params["blocks"], jnp.arange(n_macro), state["macro_kv"]))
        new_state["macro_rglru"] = rg_sts
        new_state["macro_kv"] = A.write_tokens(state["macro_kv"], new_kv,
                                               cache_len, ring)
    else:
        pre_kv = []
        for i in range(cfg.n_dense_layers):
            blk, kv = jax.tree_util.tree_map(
                lambda a: a[i], (params["dense_blocks"], state["pre_kv"]))
            x1, tok = _dense_block_decode(x1, blk, kv, cache_len, cfg, plan)
            pre_kv.append(tok)
        if pre_kv:
            new_state["pre_kv"] = A.write_tokens(
                state["pre_kv"],
                jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *pre_kv),
                cache_len, ring)

        def body(h, xs):
            blk, kv = xs
            return _dense_block_decode(h, blk, kv, cache_len, cfg, plan)
        x1, new_kv = jax.lax.scan(body, x1, (params["blocks"], state["kv"]))
        new_state["kv"] = A.write_tokens(state["kv"], new_kv, cache_len, ring)

    logits = lm_logits(params, cfg, plan, x1)
    return logits, new_state
