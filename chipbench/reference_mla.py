"""Plain float32 reference of DeepSeek-V2 (latent attention, DeepSeekMoE),
written from the paper (arXiv:2405.04434, §2.1-2.2) and the published
``modeling_deepseek.py``, in the weight layout of ``drivers/serve_mla.py``.
It imports nothing of the program.

Per layer, for tokens h (T, d):

* attention: q = h Wq, per head [q_nope (128) | q_pe (64)];
  [c (512) | k_pe (64)] = h Wkv_a; c = RMSNorm(c); per head
  [k_nope (128) | v (128)] = c Wkv_b; YaRN rope on q_pe and on k_pe, one
  rotary key for every head, pairs (2i, 2i+1) rotated together; causal
  softmax of [q_nope | q_pe].[k_nope | k_pe] times the softmax scale
  192^-1/2 * mscale(40, 0.707)^2; the weighted sum of v; Wo;
* layers below ``first_k_dense_replace``: a SwiGLU of ``intermediate_size``;
* the others: a softmax router over ``router_experts`` outputs, the top
  ``num_experts_per_tok`` gates as they are (``norm_topk_prob`` false) times
  ``routed_scaling_factor``; the held experts (``held_first`` ..
  ``+ n_routed_experts``) only, each a SwiGLU of ``moe_intermediate_size``,
  weighted by their gates; plus the shared experts, one SwiGLU of
  ``n_shared_experts x moe_intermediate_size``.

Every matmul runs at ``Precision.HIGHEST``; no kernels, cache or batching.
To fit a 64k-token row, position-wise work runs over blocks of rows and
attention over blocks of query rows, each against every key (masked), which
changes no number.  ``low`` rounds each matmul's operands to fp8 (the
control, ``reference.fp8``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import _ein, _rms

#: rows per block of the position-wise work, and query rows per block of
#: attention (a block's scores are heads x QUERY_ROWS x T in float32)
ROWS = 4096
QUERY_ROWS = 256


def yarn_inv_freq(c: dict) -> np.ndarray:
    """YaRN inverse frequencies of the rotary dims: extrapolated below the
    correction range, interpolated by ``factor`` above it, a linear ramp
    between (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    dim, base, y = c["qk_rope_head_dim"], float(c["rope_theta"]), \
        c["rope_scaling"]

    def corr(rot):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / y["factor"]
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inter * ramp + extra * (1 - ramp)


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(c: dict) -> float:
    y = c["rope_scaling"]
    s = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    return s * mscale(y["factor"], y["mscale_all_dim"]) ** 2


def _rope(x, pos, c: dict):
    """x (T, H, D) at positions pos (T,): pairs (2i, 2i+1) rotated by
    pos * inv_freq[i], written as [rotated evens | rotated odds]."""
    y = c["rope_scaling"]
    ang = pos[:, None].astype(jnp.float32) \
        * jnp.asarray(yarn_inv_freq(c), jnp.float32)               # (T, D/2)
    m = mscale(y["factor"], y["mscale"]) / mscale(y["factor"],
                                                  y["mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * m)[:, None], (jnp.sin(ang) * m)[:, None]
    ev, od = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([ev * cos - od * sin, od * cos + ev * sin], -1)


def _by_rows(f, x, n: int = ROWS):
    """f over blocks of ``n`` rows of x (T, ...), padded and trimmed."""
    t = x.shape[0]
    pad = (-t) % n
    xb = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    out = lax.map(f, xb.reshape((-1, n) + x.shape[1:]))
    return out.reshape((-1,) + out.shape[2:])[:t]


def _swiglu(h, m: dict, low: bool):
    return _ein("tf,fd->td", jax.nn.silu(_ein("td,df->tf", h, m["w_gate"], low))
                * _ein("td,df->tf", h, m["w_up"], low), m["w_down"], low)


def _attention(c: dict, low: bool, h, a: dict):
    t = h.shape[0]
    nh, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    pos = jnp.arange(t)
    q = _ein("td,de->te", h, a["wq"], low).reshape(t, nh, dn + dr)
    kv_a = _ein("td,de->te", h, a["wkv_a"], low)
    lat = _rms(kv_a[:, :r], a["kv_norm"], c["rms_norm_eps"])
    k_pe = _rope(kv_a[:, None, r:], pos, c)                         # (T,1,dr)
    kv = _ein("tr,re->te", lat, a["wkv_b"], low).reshape(t, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, c)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (t, nh, dr))],
                        -1)
    v = kv[..., dn:]
    scale = softmax_scale(c)

    def block(qp):
        qb, pb = qp
        s = _ein("qhe,khe->hqk", qb, k, low) * scale
        s = jnp.where(pos[None, None, :] <= pb[None, :, None], s, -jnp.inf)
        return _ein("hqk,khv->qhv", jax.nn.softmax(s, -1), v, low)

    n = QUERY_ROWS
    pad = (-t) % n
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, n, nh, dn + dr)
    pb = jnp.pad(pos, (0, pad)).reshape(-1, n)
    o = lax.map(block, (qb, pb)).reshape(-1, nh * dv)[:t]
    return _ein("te,ed->td", o, a["wo"], low)


def _experts(c: dict, low: bool, h, p: dict):
    """The held experts' weighted outputs plus the shared experts'."""
    k, first, held = (c["num_experts_per_tok"], c["held_first"],
                      c["n_routed_experts"])
    probs = jax.nn.softmax(_ein("td,de->te", h, p["w_router"], low), -1)
    gates, idx = lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * c["routed_scaling_factor"]
    weight = jnp.sum(jax.nn.one_hot(idx, c["router_experts"])
                     * gates[..., None], 1)[:, first:first + held]   # (T,held)

    def one(acc, e):
        ye = _swiglu(h, {w: p[w][e] for w in ("w_gate", "w_up", "w_down")},
                     low)
        return acc + weight[:, e, None] * ye, None

    out, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    return out + _swiglu(h, p["shared"], low)


def _layer(c: dict, low: bool, x, blk: dict, moe: bool):
    eps = c["rms_norm_eps"]
    x = x + _attention(c, low, _rms(x, blk["ln1"], eps), blk["attn"])

    def ffn(xb):
        h = _rms(xb, blk["ln2"], eps)
        return xb + (_experts(c, low, h, blk["moe"]) if moe
                     else _swiglu(h, blk["mlp"], low))

    return _by_rows(ffn, x)


@functools.partial(jax.jit, static_argnames=("cfg_items", "n_out", "low"))
def _decoder_logits(params, tokens, cfg_items: tuple, n_out: int, low: bool):
    c = dict(cfg_items)
    c["rope_scaling"] = dict(c["rope_scaling"])

    def row(tok):
        x = params["embed"].astype(jnp.float32)[tok]
        for i in range(c["first_k_dense_replace"]):
            x = _layer(c, low, x, jax.tree.map(lambda a: a[i],
                                                params["dense_blocks"]), False)
        x, _ = lax.scan(lambda x, blk: (_layer(c, low, x, blk, True), None),
                        x, params["blocks"])
        h = _rms(x[-n_out:], params["final_norm"], c["rms_norm_eps"])
        return _ein("td,vd->tv", h, params["lm_head"], low)

    return lax.map(row, tokens)


_KEYS = ("hidden_size", "num_attention_heads", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
         "rope_theta", "first_k_dense_replace", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor", "router_experts",
         "held_first", "n_routed_experts")


def _hashable(c: dict) -> tuple:
    return tuple((k, c[k]) for k in _KEYS) \
        + (("rope_scaling", tuple(sorted(c["rope_scaling"].items()))),)


def decoder_logits(params, c: dict, tokens, n_out: int, low: bool = False):
    """float32 logits at the last ``n_out`` positions of each row of
    ``tokens`` (R, T), under a full causal forward."""
    return _decoder_logits(params, jnp.asarray(tokens, jnp.int32),
                           _hashable(c), n_out, low)
