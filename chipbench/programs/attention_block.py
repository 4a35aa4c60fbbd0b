"""The program the planning cells hand to the jaxpr frontend: causal GQA
softmax attention, output projection, residual add and RMSNorm, as a user
would write it in plain ``jax.numpy``.  Its inputs are made on the device
from the seed."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


@jax.jit
def attention(q, k, v):
    """Causal GQA softmax attention: q (B,S,Hq,D), k/v (B,S,Hkv,D)."""
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@jax.jit
def rmsnorm(x, scale):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + 1e-6)
            * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def attention_block(x, scale, wo, q, k, v):
    """Attention, output projection, residual add, rmsnorm."""
    b, s = q.shape[:2]
    o = attention(q, k, v).reshape(b, s, -1)
    return rmsnorm(x + o @ wo, scale)


@jax.jit(static_argnames=("batch", "seq", "n_heads", "n_kv_heads",
                          "head_dim", "d_model"))
def make_args(key, *, batch: int, seq: int, n_heads: int, n_kv_heads: int,
              head_dim: int, d_model: int) -> tuple:
    """Seeded bf16 inputs of :func:`attention_block`, in one call."""
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (batch, seq, d_model), jnp.float32)
    scale = 0.1 * jax.random.normal(ks[1], (d_model,), jnp.float32)
    wo = jax.random.normal(ks[2], (n_heads * head_dim, d_model),
                           jnp.float32) / math.sqrt(n_heads * head_dim)
    q = jax.random.normal(ks[3], (batch, seq, n_heads, head_dim), jnp.float32)
    k = jax.random.normal(ks[4], (batch, seq, n_kv_heads, head_dim),
                          jnp.float32)
    v = jax.random.normal(ks[5], (batch, seq, n_kv_heads, head_dim),
                          jnp.float32)
    return tuple(a.astype(jnp.bfloat16) for a in (x, scale, wo, q, k, v))
