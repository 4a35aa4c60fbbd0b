"""Seeded random weights of a decoder configuration, made on the device in
one jitted call, in the type they are served in.

The tree has the layout the serving program takes (stacked per-layer
leaves under ``blocks``); the benchmark makes it, so the program under test
and the reference read the same numbers and neither made them.  Norm
weights are stored as offsets from 1 (a norm multiplies by ``1 + w``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import head_dim


def key_from_seed(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    too): the seed is hashed, so nearby seeds give unrelated keys."""
    word = np.random.SeedSequence([int(seed), stream]).generate_state(1)[0]
    return jax.random.key(int(word))


def shapes(c: dict) -> dict:
    """name path -> (shape, dtype name) of every weight."""
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    hd, nq, nkv = head_dim(c), c["num_attention_heads"], c["num_key_value_heads"]
    ff = c["intermediate_size"]
    out = {("embed",): ((v, d), "bf16"), ("final_norm",): ((d,), "bf16")}
    if not c["tie_word_embeddings"]:
        out[("lm_head",)] = ((v, d), "bf16")
    b = ("blocks",)
    out[b + ("ln1",)] = ((n, d), "bf16")
    out[b + ("ln2",)] = ((n, d), "bf16")
    out[b + ("attn", "wq")] = ((n, d, nq * hd), "bf16")
    out[b + ("attn", "wk")] = ((n, d, nkv * hd), "bf16")
    out[b + ("attn", "wv")] = ((n, d, nkv * hd), "bf16")
    out[b + ("attn", "wo")] = ((n, nq * hd, d), "bf16")
    if c.get("qk_norm") == "per_head":
        out[b + ("attn", "q_norm")] = ((n, hd), "bf16")
        out[b + ("attn", "k_norm")] = ((n, hd), "bf16")
    e = c.get("num_experts") or 0
    if e:
        out[b + ("moe", "w_router")] = ((n, d, e), "f32")
        out[b + ("moe", "w_gate")] = ((n, e, d, ff), "bf16")
        out[b + ("moe", "w_up")] = ((n, e, d, ff), "bf16")
        out[b + ("moe", "w_down")] = ((n, e, ff, d), "bf16")
    else:
        out[b + ("mlp", "w_gate")] = ((n, d, ff), "bf16")
        out[b + ("mlp", "w_up")] = ((n, d, ff), "bf16")
        out[b + ("mlp", "w_down")] = ((n, ff, d), "bf16")
    return out


def _scale(path: tuple, shape: tuple) -> float:
    name = path[-1]
    if name in ("embed", "lm_head"):
        return 0.02
    if "norm" in name or name in ("ln1", "ln2"):
        return 0.1                 # offsets from 1
    return 1.0 / np.sqrt(shape[-2])          # fan-in


_DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec: tuple) -> list:
    keys = jax.random.split(key, len(spec))
    return [(_scale(path, shape) * jax.random.normal(k, shape, jnp.float32)
             ).astype(_DTYPES[dt]) for k, (path, shape, dt) in zip(keys, spec)]


def make(c: dict, seed: int) -> dict:
    """The weight tree of configuration ``c`` for ``seed``."""
    spec = tuple((path, shape, dt) for path, (shape, dt) in
                 sorted(shapes(c).items()))
    leaves = _make(key_from_seed(seed, 1), spec)
    tree: dict = {}
    for (path, _, _), leaf in zip(spec, leaves):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree
