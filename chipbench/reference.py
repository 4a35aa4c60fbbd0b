"""Plain float32 references the benchmark compares the program against.

Written from the published descriptions, in the weight layout of
``weights.py``, importing nothing of the program:

* ``decoder_logits`` — a decoder-only transformer (RMSNorm, rotary
  attention with optional per-head QK-norm, SwiGLU or top-k routed SwiGLU
  experts), a full causal forward over a block of rows, logits at the last
  positions only;
* ``attention_block`` — causal GQA attention, output projection, residual
  add and RMSNorm.

Every matmul runs at ``Precision.HIGHEST``.  ``Control`` rounds each
matmul's operands to fp8 (e4m3, one scale per tensor): the lower precision
a later change might be tempted by, which the comparison has to fail.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.flops import head_dim

HIGHEST = lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def fp8(x):
    """Round to e4m3 with one scale for the whole tensor, back in float32."""
    x = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = _F8_MAX / amax
    return (x * s).astype(_F8).astype(jnp.float32) / s


def _ein(spec, a, b, low: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def _rope(x, theta: float):
    """Rotate the two halves of each head (x: R, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv        # (T, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_attention(q, k, v, low: bool):
    """q: (R,T,Hq,D), k/v: (R,T,Hkv,D) -> (R,T,Hq,D)."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = _ein("rqhd,rkhd->rhqk", q, k, low) / math.sqrt(q.shape[-1])
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return _ein("rhqk,rkhd->rqhd", jax.nn.softmax(s, -1), v, low)


def _layer(c: dict, low: bool, x, blk):
    eps, hd = c["rms_norm_eps"], head_dim(c)
    r, t, _ = x.shape
    h = _rms(x, blk["ln1"], eps)
    a = blk["attn"]
    q = _ein("rtd,de->rte", h, a["wq"], low).reshape(r, t, -1, hd)
    k = _ein("rtd,de->rte", h, a["wk"], low).reshape(r, t, -1, hd)
    v = _ein("rtd,de->rte", h, a["wv"], low).reshape(r, t, -1, hd)
    if c.get("qk_norm") == "per_head":
        q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    o = _causal_attention(q, k, v, low).reshape(r, t, -1)
    x = x + _ein("rte,ed->rtd", o, a["wo"], low)
    h = _rms(x, blk["ln2"], eps)
    if "moe" in blk:
        y = _experts(c, low, h.reshape(r * t, -1), blk["moe"]).reshape(x.shape)
    else:
        m = blk["mlp"]
        y = _ein("rtf,fd->rtd",
                 jax.nn.silu(_ein("rtd,df->rtf", h, m["w_gate"], low))
                 * _ein("rtd,df->rtf", h, m["w_up"], low), m["w_down"], low)
    return x + y


def _experts(c: dict, low: bool, h, p):
    """Top-k routed experts: softmax router, the k largest gates
    (renormalised to sum 1 where ``norm_topk_prob``), each token through
    its experts only, weighted and summed."""
    n_e, k = c["num_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(_ein("td,de->te", h, p["w_router"], low), -1)
    gates, idx = lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, n_e) * gates[..., None], 1)  # (T,E)

    def one(acc, e):
        wg, wu, wd = p["w_gate"][e], p["w_up"][e], p["w_down"][e]
        ye = _ein("tf,fd->td", jax.nn.silu(_ein("td,df->tf", h, wg, low))
                  * _ein("td,df->tf", h, wu, low), wd, low)
        return acc + weight[:, e, None] * ye, None

    out, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(n_e))
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "n_out", "low"))
def _decoder_logits(params, tokens, cfg_items: tuple, n_out: int, low: bool):
    c = dict(cfg_items)
    x = params["embed"].astype(jnp.float32)[tokens]
    x, _ = lax.scan(lambda x, blk: (_layer(c, low, x, blk), None), x,
                    params["blocks"])
    h = _rms(x[:, -n_out:], params["final_norm"], c["rms_norm_eps"])
    table = params["lm_head"] if "lm_head" in params else params["embed"]
    return _ein("rtd,vd->rtv", h, table, low)


def _hashable(c: dict) -> tuple:
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "qk_norm",
            "num_experts", "num_experts_per_tok", "norm_topk_prob")
    return tuple((k, c.get(k)) for k in keep)


def decoder_logits(params, c: dict, tokens, n_out: int, low: bool = False):
    """float32 logits at the last ``n_out`` positions of each row of
    ``tokens`` (R, T), under a full causal forward."""
    return _decoder_logits(params, jnp.asarray(tokens, jnp.int32),
                           _hashable(c), n_out, low)


def attention_block(x, scale, wo, q, k, v, low: bool = False):
    """Causal GQA softmax attention, output projection, residual add and
    RMSNorm (eps 1e-6, weight stored as an offset from 1)."""
    b, s = q.shape[:2]
    o = _causal_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), low).reshape(b, s, -1)
    return _rms(x.astype(jnp.float32) + _ein("bse,ed->bsd", o, wo, low),
                scale, 1e-6)


attention_block_jit = jax.jit(attention_block, static_argnames=("low",))


def widest_gap(ref_logits, tokens) -> np.ndarray:
    """Per position: how far the logit of ``tokens`` lies below the best
    logit, both read from ``ref_logits`` (R, N, V); tokens (R, N)."""
    ref = np.asarray(ref_logits, np.float32)
    chosen = np.take_along_axis(ref, np.asarray(tokens)[..., None], -1)[..., 0]
    return ref.max(-1) - chosen


def rel_l2(got, want) -> np.ndarray:
    """Relative L2 error over the last axis: one value per row."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    num = np.linalg.norm((got - want).reshape(-1, want.shape[-1]), axis=-1)
    den = np.linalg.norm(want.reshape(-1, want.shape[-1]), axis=-1)
    return num / np.maximum(den, 1e-30)
