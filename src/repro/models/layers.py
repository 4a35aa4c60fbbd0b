"""Shared neural layers: norms, rotary embeddings, gated MLPs, embeddings.

Every layer has a ``ref`` implementation (plain jnp, the "CPU path" of the
paper) and, where profitable, a ``fused`` implementation (the "offloaded"
path — a fused-jnp rewrite on CPU/dry-run, a Pallas kernel on real TPU; see
``repro.kernels``).  Implementation choice comes from the :class:`ExecPlan`.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.plan import ExecPlan

Array = jax.Array


def cdtype(plan: ExecPlan):
    return jnp.dtype(plan.compute_dtype)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis: int = -2, dtype=jnp.float32) -> Array:
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32) -> Array:
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_ref(x: Array, scale: Array, eps: float) -> Array:
    """Reference: upcast, normalize, scale (separate ops)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rmsnorm_fused(x: Array, scale: Array, eps: float) -> Array:
    """Fused formulation (single-pass; Pallas kernel `kernels/rmsnorm.py` on TPU).

    Numerically identical to the reference — one fused expression lets XLA
    emit a single loop; on TPU the pattern DB swaps in the Pallas kernel.
    """
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rmsnorm(x: Array, scale: Array, eps: float, plan: ExecPlan) -> Array:
    with jax.named_scope("norm"):
        if plan.norm_impl == "fused":
            return rmsnorm_fused(x, scale, eps)
        return rmsnorm_ref(x, scale, eps)


def layernorm(x: Array, scale: Array, bias: Array, eps: float) -> Array:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * scale.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)  # (head_dim/2,)


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, D); positions: (..., S)."""
    if theta <= 0:
        return x
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,D/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, yarn) -> tuple[int, int]:
    """The rotary dims between which YaRN ramps from extrapolated to
    interpolated frequencies (DeepSeek-V2's ``yarn_find_correction_range``)."""
    def dim_of(rotations: float) -> float:
        return dim * math.log(yarn.original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = math.floor(dim_of(yarn.beta_fast))
    high = math.ceil(dim_of(yarn.beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """(dim/2,) inverse frequencies: the extrapolated ones below the
    correction range, the ones interpolated by ``factor`` above it, a linear
    ramp between."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / yarn.factor
    low, high = yarn_correction_range(dim, theta, yarn)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def apply_rope_pairs(x: Array, positions: Array,
                     inv_freq: np.ndarray) -> Array:
    """Rotary embedding of adjacent pairs (2i, 2i+1), DeepSeek-V2's
    convention; x: (..., S, H, D), positions: (S,).  The result is in
    halves layout ``[rotated evens | rotated odds]``, as upstream emits it
    (de-interleave, then rotate halves); the same permutation on q and k
    leaves their dot products unchanged."""
    angles = positions[:, None, None].astype(jnp.float32) \
        * jnp.asarray(inv_freq)                          # (S,1,D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf = x.astype(jnp.float32)
    ev, od = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([ev * cos - od * sin, od * cos + ev * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def _act(x: Array, kind: str) -> Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu_sq":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(kind)


def mlp_init(key, d_model: int, d_ff: int, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype=dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype=dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype=dtype),
    }


def _ff_constrain(h: Array) -> Array:
    """Pin the (..., ff) hidden to TP-column sharding so XLA gathers the
    (small) w_down weight, never the (huge) activation.  Rank-agnostic:
    (B,S,ff) for dense layers, (T,ff) for the shared-expert path."""
    from repro.runtime.pspec import constrain
    axes = ("batch",) + (None,) * (h.ndim - 2) + ("tensor",)
    return constrain(h, *axes)


def mlp_ref(x: Array, p: dict, act: str, plan: ExecPlan) -> Array:
    """Reference: three separate matmuls."""
    dt = cdtype(plan)
    g = _ff_constrain(x @ p["w_gate"].astype(dt))
    u = _ff_constrain(x @ p["w_up"].astype(dt))
    return _ff_constrain(_act(g, act) * u) @ p["w_down"].astype(dt)


def mlp_fused(x: Array, p: dict, act: str, plan: ExecPlan) -> Array:
    """Fused: gate+up as ONE matmul (halves weight re-reads; MXU-friendly)."""
    dt = cdtype(plan)
    wgu = jnp.concatenate([p["w_gate"], p["w_up"]], axis=1).astype(dt)
    gu = x @ wgu
    g, u = jnp.split(gu, 2, axis=-1)
    return _ff_constrain(_act(_ff_constrain(g), act) * _ff_constrain(u)) \
        @ p["w_down"].astype(dt)


def mlp(x: Array, p: dict, act: str, plan: ExecPlan) -> Array:
    with jax.named_scope("mlp"):
        if plan.mlp_impl == "fused":
            return mlp_fused(x, p, act, plan)
        return mlp_ref(x, p, act, plan)


# ---------------------------------------------------------------------------
# Embedding + logits
# ---------------------------------------------------------------------------


def embed_tokens(tokens: Array, table: Array, plan: ExecPlan, scale: bool) -> Array:
    x = jnp.take(table, tokens, axis=0).astype(cdtype(plan))
    if scale:
        x = x * jnp.asarray(np.sqrt(table.shape[1]), x.dtype)
    return x


def logits_from_hidden(h: Array, table: Array, plan: ExecPlan, softcap: float) -> Array:
    out = h @ table.T.astype(cdtype(plan))
    if softcap > 0:
        out = jnp.tanh(out / softcap) * softcap
    return out


def cross_entropy_full(logits: Array, labels: Array) -> Array:
    """Reference loss: materialize full (B,S,V) fp32 log-softmax.

    Returns per-token nll (B,S); caller applies the loss mask.
    """
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]


def cross_entropy_chunked(h: Array, table: Array, labels: Array, plan: ExecPlan,
                          softcap: float) -> Array:
    """Memory-lean loss: never materialize fp32 (B,S,V).

    Computes logsumexp and the label logit by scanning vocab chunks; peak
    live memory is (B,S,chunk) instead of (B,S,V).  This is the "offloaded"
    loss region.  The hidden states are sequence-sharded over "model" so the
    per-chunk logits tensor shards too.
    """
    from repro.runtime.pspec import constrain
    h = constrain(h, "batch", "seq_sp", None)
    labels = constrain(labels, "batch", "seq_sp")
    v = table.shape[0]
    chunk = min(plan.loss_vocab_chunk, v)
    n_chunks = -(-v // chunk)
    pad_v = n_chunks * chunk
    tbl = jnp.pad(table, ((0, pad_v - v), (0, 0))) if pad_v != v else table
    tbl = tbl.reshape(n_chunks, chunk, table.shape[1])

    def body(carry, tchunk_i):
        m, s, lbl_logit, idx = carry
        tchunk, ci = tchunk_i
        lg = (h @ tchunk.T.astype(h.dtype)).astype(jnp.float32)  # (B,S,chunk)
        if softcap > 0:
            lg = jnp.tanh(lg / softcap) * softcap
        # mask padding columns
        col = ci * chunk + jnp.arange(chunk)
        lg = jnp.where(col[None, None, :] < v, lg, -jnp.inf)
        new_m = jnp.maximum(m, jnp.max(lg, axis=-1))
        s = s * jnp.exp(m - new_m) + jnp.sum(jnp.exp(lg - new_m[..., None]), axis=-1)
        # pick up the label logit if it lives in this chunk
        rel = labels - ci * chunk
        in_chunk = (rel >= 0) & (rel < chunk)
        picked = jnp.take_along_axis(lg, jnp.clip(rel, 0, chunk - 1)[..., None], axis=-1)[..., 0]
        lbl_logit = jnp.where(in_chunk, picked, lbl_logit)
        return (new_m, s, lbl_logit, idx), None

    b, s_len = labels.shape
    init = (
        jnp.full((b, s_len), -jnp.inf, jnp.float32),
        jnp.zeros((b, s_len), jnp.float32),
        jnp.zeros((b, s_len), jnp.float32),
        0,
    )
    (m, ssum, lbl_logit, _), _ = jax.lax.scan(
        body, init, (tbl, jnp.arange(n_chunks)))
    lse = m + jnp.log(ssum)
    return lse - lbl_logit  # per-token nll (B,S); caller applies the mask
