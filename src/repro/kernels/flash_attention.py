"""Pallas TPU flash attention (causal / full, GQA via index-mapped KV heads).

TPU-native adaptation of the paper's "function-block offload" target: the
pattern DB replaces the softmax-attention block with this kernel on TPU
(the chunked-jnp twin `models/attention.attend_chunked` is the portable
fallback the dry-run lowers).

Tiling: grid = (B*Hq, nQ, nK) with the KV axis sequential ("arbitrary");
online-softmax stats (m, l) and the output accumulator live in VMEM scratch
that persists across the KV axis.  Causal blocks strictly above the diagonal
are skipped with `pl.when` — on real TPU this prunes ~half the MXU work,
which the pure-XLA fallback cannot do (see DESIGN.md §Hardware-adaptation).

Block sizes must divide the (padded) sequence lengths; `ops.flash_attention`
pads and picks MXU-aligned blocks (multiples of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, blk_q: int, blk_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip blocks entirely above the diagonal
    live = (ki * blk_k <= qi * blk_q + blk_q - 1) if causal else (ki >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (blk_q, D)
        k = k_ref[0].astype(jnp.float32)          # (blk_k, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (blk_q, blk_k)
        if causal:
            rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_scr[...]                        # (blk_q, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                     # (blk_q, blk_k)
        corr = jnp.exp(m_prev - m_new)             # (blk_q, 1)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (blk_q, D)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)).astype(o_ref.dtype)


def flash_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool, scale: float, blk_q: int = 128,
                       blk_k: int = 128, group: int = 1,
                       interpret: bool) -> jax.Array:
    """q: (B*Hq, Sq, D); k, v: (B*Hkv, Sk, D); Hq = Hkv * group.

    Returns (B*Hq, Sq, D).  Sequence lengths must be multiples of the block
    sizes (ops.py pads).
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % blk_q == 0 and sk % blk_k == 0, (sq, sk, blk_q, blk_k)
    nq, nk = sq // blk_q, sk // blk_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, qi, ki, g=group: (b // g, ki, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, qi, ki, g=group: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(q, k, v)
