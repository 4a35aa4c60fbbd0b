"""Pallas TPU kernel for the RG-LRU linear recurrence  h_t = a_t h_{t-1} + b_t.

Grid: (batch, channel_blocks, time_chunks) with the time axis sequential.
The carried state h lives in VMEM scratch across time chunks; within a chunk
the inclusive scan runs as a log2(chunk) doubling pass over VPU lanes —
no per-step HBM round trips, unlike the lax.scan reference.

Channel blocks are lane-aligned (multiples of 128); chunk length must divide
the sequence (ops.py pads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_scan(log_a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Inclusive scan of h_t = exp(log_a_t) h_{t-1} + b_t within one chunk.

    Doubling pass: after round r each row t combines inputs (t-2^r, t].
    Identity element is (log_a=0, b=0).
    """
    c = log_a.shape[0]
    la, bb = log_a, b
    shift = 1
    while shift < c:
        la_s = jnp.pad(la, ((shift, 0), (0, 0)))[:c]
        bb_s = jnp.pad(bb, ((shift, 0), (0, 0)))[:c]
        bb = jnp.exp(la) * bb_s + bb
        la = la + la_s
        shift *= 2
    return la, bb  # cumulative (log_a products, scanned b with h0=0)


def _rglru_kernel(log_a_ref, b_ref, out_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    la = log_a_ref[0].astype(jnp.float32)   # (chunk, d_blk)
    bb = b_ref[0].astype(jnp.float32)
    la_cum, b_cum = _chunk_scan(la, bb)
    h = jnp.exp(la_cum) * h_scr[...] + b_cum  # (chunk, d_blk): all states
    out_ref[0] = h.astype(out_ref.dtype)
    h_scr[...] = h[-1:, :]


def rglru_scan(log_a: jax.Array, b: jax.Array, *, chunk: int = 256,
               d_block: int = 128, interpret: bool) -> jax.Array:
    """log_a, b: (B, S, D) -> states h: (B, S, D).  h0 = 0 (ops.py folds a
    nonzero initial state into b[0])."""
    bsz, s, d = log_a.shape
    assert s % chunk == 0 and d % d_block == 0, (s, d, chunk, d_block)
    grid = (bsz, d // d_block, s // chunk)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b_, di, ci: (b_, ci, di)),
            pl.BlockSpec((1, chunk, d_block), lambda b_, di, ci: (b_, ci, di)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda b_, di, ci: (b_, ci, di)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, d_block), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
        **kwargs,
    )(log_a, b)
