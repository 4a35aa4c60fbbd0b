"""Pallas TPU kernel for the RWKV-6 WKV recurrence (data-dependent decay).

Per head with state S in R^{DxD}:
    y_t = r_t^T (S_{t-1} + (u * k_t) outer v_t)
    S_t = diag(w_t) S_{t-1} + k_t outer v_t

Grid: (B*H, time_chunks), time sequential; the DxD state persists in VMEM
scratch.  Within a chunk (length C) the recurrence is evaluated in closed
form with log-space decay ratios (all exponents <= 0, numerically safe):

    cs_t   = cumsum(log w) (inclusive),  cs'_t = cs_t - log w_t (exclusive)
             (a lower-triangular-ones matmul: Mosaic has no cumsum)
    inter  = (r_t * exp(cs'_t)) @ S_in
    intra  = tril_{-1}[ (r_t * exp(cs'_t)) (k_s * exp(-cs_s))^T ] @ V
    bonus  = (r_t . u . k_t) v_t
    S_out  = exp(cs_C) S_in + (K * exp(cs_C - cs))^T V

D=64 and C=64 give MXU-shaped (64,64) matmuls; head dim must equal the
block D (ops.py asserts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)    # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)  # (C, D), <= 0
    u = u_ref[0].astype(jnp.float32)    # (1, D)
    s_in = s_scr[...]                   # (D, D)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = jnp.where(cols <= rows, 1.0, 0.0).astype(jnp.float32)
    cs = jax.lax.dot_general(           # inclusive prefix sum over time
        tril, lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    cs_prev = cs - lw                   # exclusive
    r_dec = r * jnp.exp(cs_prev)        # (C, D)
    k_dec = k * jnp.exp(-cs)            # (C, D)

    y_inter = jax.lax.dot_general(
        r_dec, s_in, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    scores = jax.lax.dot_general(
        r_dec, k_dec, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    scores = jnp.where(cols < rows, scores, 0.0)   # strictly lower triangle
    y_intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y_diag = jnp.sum(r * u * k, axis=-1, keepdims=True) * v
    o_ref[0] = (y_inter + y_intra + y_diag).astype(o_ref.dtype)

    cs_last = cs[-1:, :]                # (1, D)
    k_tail = k * jnp.exp(cs_last - cs)  # (C, D)
    s_new = jnp.exp(cs_last[0])[:, None] * s_in + jax.lax.dot_general(
        k_tail, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s_scr[...] = s_new


def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array,
         u: jax.Array, *, chunk: int = 64, interpret: bool) -> jax.Array:
    """r/k/v/log_w: (BH, S, D); u: (BH, 1, D).  Returns y: (BH, S, D) fp32."""
    bh, s, d = r.shape
    assert s % chunk == 0, (s, chunk)
    grid = (bh, s // chunk)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    specs = [pl.BlockSpec((1, chunk, d), lambda b, ci: (b, ci, 0))] * 4
    specs.append(pl.BlockSpec((1, 1, d), lambda b, ci: (b, 0, 0)))
    return pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=grid,
        in_specs=specs,
        out_specs=pl.BlockSpec((1, chunk, d), lambda b, ci: (b, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
        name="wkv6",
        **kwargs,
    )(r, k, v, log_w, u)
