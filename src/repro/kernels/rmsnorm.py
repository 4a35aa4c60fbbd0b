"""Pallas TPU fused RMSNorm: one pass over rows, f32 statistics.

Grid over row blocks; each invocation loads a (blk_rows, d) tile into VMEM,
computes rsqrt(mean(x^2)+eps) and writes x * inv * (1 + scale) — a single
fused loop instead of the reference's separate square/mean/rsqrt/mul ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)             # (blk, d)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (x * inv * (1.0 + s_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            blk_rows: int = 256, interpret: bool) -> jax.Array:
    """x: (N, d); scale: (d,)."""
    n, d = x.shape
    blk = min(blk_rows, n)
    assert n % blk == 0, (n, blk)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n // blk,),
        in_specs=[
            pl.BlockSpec((blk, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
        **kwargs,
    )(x, scale)
