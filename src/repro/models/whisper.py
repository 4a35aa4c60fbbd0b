"""Whisper-small backbone: transformer encoder-decoder.

The conv/mel frontend is a STUB per the assignment: ``input_specs()`` feeds
precomputed frame embeddings (B, encoder_seq, d_model).  Positions are
sinusoidal (computed on the fly, so any decoder length works).  Decoder
blocks: causal self-attention + cross-attention to the encoder output + MLP.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models.plan import ExecPlan
from repro.models.transformer import _maybe_remat, _stack_init
from repro.runtime.pspec import constrain

Array = jax.Array


def sinusoid_positions(s: int, d: int, offset=0) -> Array:
    pos = jnp.arange(s, dtype=jnp.float32) + offset
    inv = jnp.exp(-jnp.arange(0, d, 2, dtype=jnp.float32) / d * np.log(10000.0))
    ang = pos[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_block_init(key, cfg: ArchConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": A.attn_init(k1, cfg, dtype=dtype),
        "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype),
    }


def _dec_block_init(key, cfg: ArchConfig, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln_x": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": A.attn_init(k1, cfg, dtype=dtype),
        "xattn": A.attn_init(k3, cfg, dtype=dtype),
        "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype),
    }


def init_params(cfg: ArchConfig, rng: jax.Array, dtype=jnp.float32) -> dict:
    ke, kd, kt = jax.random.split(rng, 3)
    return {
        "embed": L.embed_init(kt, (cfg.vocab, cfg.d_model), dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
        "enc_final_norm": jnp.zeros((cfg.d_model,), dtype),
        "enc_blocks": _stack_init(ke, cfg.n_encoder_layers,
                                  lambda k: _enc_block_init(k, cfg, dtype)),
        "blocks": _stack_init(kd, cfg.n_layers,
                              lambda k: _dec_block_init(k, cfg, dtype)),
    }


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------


def encode(params: dict, cfg: ArchConfig, plan: ExecPlan, frames: Array) -> Array:
    """frames: (B, T_enc, d) stub embeddings -> (B, T_enc, d)."""
    dt = L.cdtype(plan)
    t_enc = frames.shape[1]
    x = frames.astype(dt) + sinusoid_positions(t_enc, cfg.d_model).astype(dt)
    x = constrain(x, "batch", "seq", None)
    positions = jnp.arange(t_enc, dtype=jnp.int32)

    def body(carry, blk):
        h = L.rmsnorm(carry, blk["ln1"], cfg.norm_eps, plan)
        q, k, v = A.project_qkv(h, blk["attn"], cfg, plan, positions)
        o = A.attend(q, k, v, positions, positions, causal=False,
                     attn_kind="full", window=0, plan=plan)
        o = o.reshape(*carry.shape[:2], -1) @ blk["attn"]["wo"].astype(dt)
        x1 = carry + constrain(o, "batch", "seq", None)
        h2 = L.rmsnorm(x1, blk["ln2"], cfg.norm_eps, plan)
        return x1 + L.mlp(h2, blk["mlp"], cfg.mlp_act, plan), jnp.zeros((), jnp.float32)

    body = _maybe_remat(body, plan)
    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return L.rmsnorm(x, params["enc_final_norm"], cfg.norm_eps, plan)


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------


def _dec_block_full(x, blk, enc_out, cfg, plan, positions, want_cache, cache_capacity):
    dt = L.cdtype(plan)
    b, s, _ = x.shape
    enc_pos = jnp.arange(enc_out.shape[1], dtype=jnp.int32)
    # self attention
    h = L.rmsnorm(x, blk["ln1"], cfg.norm_eps, plan)
    q, k, v = A.project_qkv(h, blk["attn"], cfg, plan, positions)
    o = A.attend(q, k, v, positions, positions, causal=True,
                 attn_kind="full", window=0, plan=plan)
    x = x + (o.reshape(b, s, -1) @ blk["attn"]["wo"].astype(dt))
    # cross attention
    hx = L.rmsnorm(x, blk["ln_x"], cfg.norm_eps, plan)
    qx = A.project_q(hx, blk["xattn"], cfg, plan, positions)
    kx, vx = A.project_kv(enc_out, blk["xattn"], cfg, plan, enc_pos)
    ox = A.attend(qx, kx, vx, positions, enc_pos, causal=False,
                  attn_kind="full", window=0, plan=plan)
    x = x + (ox.reshape(b, s, -1) @ blk["xattn"]["wo"].astype(dt))
    # mlp
    h2 = L.rmsnorm(x, blk["ln2"], cfg.norm_eps, plan)
    x = x + L.mlp(h2, blk["mlp"], cfg.mlp_act, plan)
    cache = None
    if want_cache:
        t_enc = enc_out.shape[1]
        cache = {"k": A.to_cache(k, cache_capacity),
                 "v": A.to_cache(v, cache_capacity),
                 "xk": A.to_cache(kx, t_enc), "xv": A.to_cache(vx, t_enc)}
    return x, cache


def decoder_forward(params, cfg, plan, tokens, enc_out, want_cache=False,
                    cache_capacity: int = 0):
    dt = L.cdtype(plan)
    s = tokens.shape[1]
    cache_capacity = cache_capacity or s
    x = L.embed_tokens(tokens, params["embed"], plan, False)
    x = x + sinusoid_positions(s, cfg.d_model).astype(dt)
    x = constrain(x, "batch", "seq", None)
    positions = jnp.arange(s, dtype=jnp.int32)

    def body(carry, blk):
        h, cache = _dec_block_full(carry, blk, enc_out, cfg, plan, positions,
                                   want_cache, cache_capacity)
        return h, (cache if want_cache else jnp.zeros((), jnp.float32))

    body = _maybe_remat(body, plan)
    x, caches = jax.lax.scan(body, x, params["blocks"])
    return x, (caches if want_cache else None)


def lm_loss(params: dict, batch: dict, cfg: ArchConfig, plan: ExecPlan):
    enc_out = encode(params, cfg, plan, batch["frames"])
    hidden, _ = decoder_forward(params, cfg, plan, batch["tokens"], enc_out)
    hidden = L.rmsnorm(hidden, params["final_norm"], cfg.norm_eps, plan)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    if plan.loss_impl == "chunked_vocab":
        nll = L.cross_entropy_chunked(hidden, params["embed"], safe, plan, 0.0)
    else:
        logits = L.logits_from_hidden(hidden, params["embed"], plan, 0.0)
        logits = constrain(logits, "batch", "seq", "vocab")
        nll = L.cross_entropy_full(logits, safe)
    ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return ce, {"ce": ce, "loss": ce}


def prefill(params: dict, cfg: ArchConfig, plan: ExecPlan, tokens: Array,
            frames: Array, cache_capacity: int = 0):
    enc_out = encode(params, cfg, plan, frames)
    hidden, caches = decoder_forward(params, cfg, plan, tokens, enc_out,
                                     want_cache=True,
                                     cache_capacity=cache_capacity or tokens.shape[1])
    h = L.rmsnorm(hidden[:, -1:], params["final_norm"], cfg.norm_eps, plan)
    logits = L.logits_from_hidden(h, params["embed"], plan, 0.0)
    state = {"dec": caches, "cache_len": jnp.asarray(tokens.shape[1], jnp.int32)}
    return logits, state


def decode_step(params: dict, cfg: ArchConfig, plan: ExecPlan, token: Array,
                state: dict):
    """The decoder's step, on the cache scheme of ``transformer.decode_step``:
    the self-attention and cross-attention caches are read-only scan ``xs``;
    the token's k/v come out as ``ys`` and are written once after the scan.
    The cross-attention cache (``xk``/``xv``) is passed through unchanged."""
    dt = L.cdtype(plan)
    cache_len = state["cache_len"]
    b = token.shape[0]
    x1 = L.embed_tokens(token, params["embed"], plan, False)
    x1 = x1 + sinusoid_positions(1, cfg.d_model, offset=cache_len).astype(dt)
    pos = cache_len[None].astype(jnp.int32)

    def body(x, xs):
        blk, kv = xs
        h = L.rmsnorm(x, blk["ln1"], cfg.norm_eps, plan)
        with jax.named_scope("attention"):
            q, k, v = A.project_qkv(h, blk["attn"], cfg, plan, pos)
            valid = A.decode_valid(kv["k"].shape[2], cache_len, False)
            o = A.attend_decode(q, A.KVCache(kv["k"], kv["v"]), valid, plan, k, v)
            x = x + (o.reshape(b, 1, -1) @ blk["attn"]["wo"].astype(dt))
        hx = L.rmsnorm(x, blk["ln_x"], cfg.norm_eps, plan)
        with jax.named_scope("attention"):
            qx = A.project_q(hx, blk["xattn"], cfg, plan, pos)
            ox = A.attend_decode(qx, A.KVCache(kv["xk"], kv["xv"]), None, plan)
            x = x + (ox.reshape(b, 1, -1) @ blk["xattn"]["wo"].astype(dt))
        h2 = L.rmsnorm(x, blk["ln2"], cfg.norm_eps, plan)
        x = x + L.mlp(h2, blk["mlp"], cfg.mlp_act, plan)
        return x, {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3)}

    dec = state["dec"]
    x1, new_kv = jax.lax.scan(body, x1, (params["blocks"], dec))
    h = L.rmsnorm(x1, params["final_norm"], cfg.norm_eps, plan)
    logits = L.logits_from_hidden(h, params["embed"], plan, 0.0)
    dec = {**dec, **A.write_tokens(dec, new_kv, cache_len, False)}
    return logits, {"dec": dec, "cache_len": cache_len + 1}
