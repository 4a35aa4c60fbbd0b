"""jit'd public wrappers for the Pallas kernels.

Handles padding to block multiples, GQA head flattening, backend detection
(interpret mode on the CPU backend only: anywhere else the kernels compile
for the device or fail loudly), and initial-state folding.
These wrappers are what the pattern DB registers as replacement
implementations for the matched function blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import rmsnorm as _rn
from repro.kernels import wkv6 as _wk


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> tuple[jax.Array, int]:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, blk_q: int = 128, blk_k: int = 128) -> jax.Array:
    """q: (B,Sq,Hq,D); k/v: (B,Sk,Hkv,D) -> (B,Sq,Hq,D)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    scale = 1.0 / np.sqrt(d)
    # flatten heads: q -> (B*Hkv*G, Sq, D) so kv index = bh // group
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, -1, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, -1, d)
    bq = min(blk_q, max(1, sq))
    bk = min(blk_k, kf.shape[1])
    out = _fa.flash_attention_bh(qf, kf, vf, causal=causal, scale=scale,
                                 blk_q=bq, blk_k=bk, group=group,
                                 interpret=_interpret())
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "d_block"))
def rglru_scan(log_a: jax.Array, b: jax.Array, h0: jax.Array | None = None, *,
               chunk: int = 256, d_block: int = 128) -> jax.Array:
    """(B,S,D) coeffs -> (B,S,D) states; optional initial state h0 (B,D)."""
    bsz, s, d = log_a.shape
    if h0 is not None:  # fold h0 into b[0]
        b = b.at[:, 0].add(jnp.exp(log_a[:, 0].astype(jnp.float32)) * h0)
    c = min(chunk, s)
    db = min(d_block, d)
    la_p, pad_s = _pad_to(log_a, 1, c)
    b_p, _ = _pad_to(b, 1, c)
    if d % db != 0:
        db = d  # fall back to one channel block
    out = _rg.rglru_scan(la_p, b_p, chunk=c, d_block=db, interpret=_interpret())
    return out[:, :s]


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array,
         u: jax.Array, *, chunk: int = 64) -> jax.Array:
    """r/k/v/log_w: (B,S,H,D); u: (H,D) -> y (B,S,H,D) f32."""
    b, s, h, d = r.shape
    def flat(a):
        return a.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    rf, kf, vf, lwf = map(flat, (r, k, v, log_w))
    uf = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, 1, d)
    c = min(chunk, s)
    # pad time to chunk multiple with log_w=0, k=0 (state-neutral)
    rf, pad = _pad_to(rf, 1, c)
    kf, _ = _pad_to(kf, 1, c)
    vf, _ = _pad_to(vf, 1, c)
    lwf, _ = _pad_to(lwf, 1, c)
    out = _wk.wkv6(rf, kf, vf, lwf, uf, chunk=c, interpret=_interpret())
    out = out[:, :s]
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("eps", "blk_rows"))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            blk_rows: int = 256) -> jax.Array:
    """x: (..., d); scale: (d,).  Rows pad to a whole number of blocks (a
    block is all rows, or ``blk_rows``, a multiple of 8 as Mosaic requires);
    zero rows normalize to zero and are sliced off."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    n = x2.shape[0]
    blk = n if n <= blk_rows else blk_rows
    x2, _ = _pad_to(x2, 0, blk)
    out = _rn.rmsnorm(x2, scale, eps=eps, blk_rows=blk, interpret=_interpret())
    return out[:n].reshape(shape)
