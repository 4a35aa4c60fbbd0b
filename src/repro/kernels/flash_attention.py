"""Pallas TPU flash attention (causal / full, GQA via index-mapped KV heads).

TPU-native adaptation of the paper's "function-block offload" target: the
pattern DB replaces the softmax-attention block with this kernel on TPU, and
the model path runs it for a long causal self-attention
(`models/attention._flash_rows`; the chunked-jnp twin `_flash` is the
portable path the CPU and the dry-run lower).

Tiling: grid = (B*Hq, nQ, nK) with the KV axis sequential ("arbitrary");
online-softmax stats (m, l) and the output accumulator live in VMEM scratch
that persists across the KV axis, so no score tile leaves the chip.  The
arithmetic is `_flash`'s: the operands go to the MXU in the dtype they come
in (bf16 q.k, then p cast to v's dtype), with f32 accumulation, the scale
applied to the f32 scores and f32 statistics.  q and k share a head dim; v
may be narrower (latent attention: 192 and 128).  Causal blocks strictly
above the diagonal are neither computed (`pl.when`) nor fetched (the K/V
index map stays on the last live block, so the pipeline issues no copy);
only blocks that cross the diagonal build the mask.

Sequence lengths are padded to the block sizes here.  Padded keys are masked
only by causality, so a non-causal call needs Sk % blk_k == 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: lanes of the log-sum-exp output block: the row statistic repeated over
#: one lane tile, so its block has the TPU's (8, 128) tiling
LSE_LANES = 128
#: (blk_q, blk_k) of a long causal self-attention, the model path's call;
#: chosen by a block-size sweep on a v5e at 16 heads x 65,536 rows, q/k 192
#: and v 128 (PERF.md, Findings)
LONG_CAUSAL_BLOCKS = (1024, 1024)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                  causal: bool, blk_q: int, blk_k: int):
    lse_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (blk_q, blk_k)
        if masked:
            rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_scr[...]                        # (blk_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                     # (blk_q, blk_k)
        corr = jnp.exp(m_prev - m_new)             # (blk_q, 1)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (blk_q, Dv)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    if causal:
        first_col, last_col = ki * blk_k, ki * blk_k + blk_k - 1
        first_row, last_row = qi * blk_q, qi * blk_q + blk_q - 1
        pl.when(last_col <= first_row)(lambda: step(False))
        pl.when((last_col > first_row) & (first_col <= last_row))(
            lambda: step(True))
    else:
        step(False)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = jnp.broadcast_to(m_scr[...] + jnp.log(l),
                                          (blk_q, LSE_LANES))


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[1]) % mult
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def flash_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool, scale: float, blk_q: int = 128,
                       blk_k: int = 128, group: int = 1, interpret: bool,
                       with_lse: bool = False):
    """q: (B*Hq, Sq, D); k: (B*Hkv, Sk, D); v: (B*Hkv, Sk, Dv);
    Hq = Hkv * group.

    Returns the (B*Hq, Sq, Dv) output in q's dtype, and with ``with_lse``
    also the (B*Hq, Sq) f32 log-sum-exp of each row's scaled scores (what a
    recomputing backward needs).
    """
    bh, sq, d = q.shape
    dv = v.shape[-1]
    if not causal:
        assert k.shape[1] % blk_k == 0, "non-causal flash requires Sk % blk_k == 0"
    q, k, v = _pad_rows(q, blk_q), _pad_rows(k, blk_k), _pad_rows(v, blk_k)
    nq, nk = q.shape[1] // blk_q, k.shape[1] // blk_k

    def kv_block(b, qi, ki, g=group):
        if causal:   # stay on the query block's last live block: no new copy
            ki = jnp.minimum(ki, (qi * blk_q + blk_q - 1) // blk_k)
        return (b // g, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k)
    out_specs = [pl.BlockSpec((1, blk_q, dv), lambda b, qi, ki: (b, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, nq * blk_q, dv), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, blk_q, LSE_LANES),
                                      lambda b, qi, ki: (b, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, nq * blk_q, LSE_LANES),
                                              jnp.float32))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    outs = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, blk_k, d), kv_block),
            pl.BlockSpec((1, blk_k, dv), kv_block),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
        **kwargs,
    )(q, k, v)
    if with_lse:
        return outs[0][:, :sq], outs[1][:, :sq, 0]
    return outs[0][:, :sq]
