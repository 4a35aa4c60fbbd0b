"""Attention: GQA/MQA, full-causal / local-window / cross, KV-cache decode.

Three interchangeable region implementations (selected by the ExecPlan — the
paper's per-loop offload gene):

* ``naive``   — materialize (Sq, Sk) scores.  Reference path.
* ``chunked`` — flash-style online softmax over KV chunks; peak memory bounded
                by the KV chunk size.  jnp twin of ``kernels/flash_attention``.
* local attention always uses the banded formulation (sub-quadratic).

All paths upcast scores to f32 for the softmax and compute matmuls in the
plan's compute dtype.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.plan import ExecPlan
from repro.runtime.pspec import constrain

Array = jax.Array
NEG_INF = -1e30


class KVCache(NamedTuple):
    """One layer's decode cache, head-major: the order the decode
    contraction reads, so a step reads it in place.  Stacked over layers it
    is (L, B, Hkv, S_cache, D)."""
    k: Array  # (B, Hkv, S_cache, D)
    v: Array  # (B, Hkv, S_cache, D)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def attn_init(key, cfg: ArchConfig, cross: bool = False, dtype=jnp.float32) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], (d, nq * hd), dtype=dtype),
        "wk": L.dense_init(ks[1], (d, nkv * hd), dtype=dtype),
        "wv": L.dense_init(ks[2], (d, nkv * hd), dtype=dtype),
        "wo": L.dense_init(ks[3], (nq * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def project_q(x: Array, p: dict, cfg: ArchConfig, plan: ExecPlan, positions: Array) -> Array:
    dt = L.cdtype(plan)
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    q = q.reshape(b, s, cfg.n_heads, hd)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps, plan)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    return q


def project_kv(x: Array, p: dict, cfg: ArchConfig, plan: ExecPlan,
               positions: Array) -> tuple[Array, Array]:
    dt = L.cdtype(plan)
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps, plan)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return k, v


def project_qkv(x: Array, p: dict, cfg: ArchConfig, plan: ExecPlan,
                positions: Array) -> tuple[Array, Array, Array]:
    """Either three matmuls (ref) or one fused qkv matmul (offloaded)."""
    dt = L.cdtype(plan)
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    if plan.qkv_fused:
        wqkv = jnp.concatenate([p["wq"], p["wk"], p["wv"]], axis=1).astype(dt)
        qkv = x @ wqkv
        if cfg.qkv_bias:
            qkv = qkv + jnp.concatenate([p["bq"], p["bk"], p["bv"]]).astype(dt)
        q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
        q = q.reshape(b, s, nq, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        if cfg.qk_norm:
            q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps, plan)
            k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps, plan)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        return q, k, v
    q = project_q(x, p, cfg, plan, positions)
    k, v = project_kv(x, p, cfg, plan, positions)
    return q, k, v


def _group(q: Array, n_kv: int) -> Array:
    """(B,S,Hq,D) -> (B,S,Hkv,G,D)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def _repeat_kv(k: Array, group: int) -> Array:
    """(B,S,Hkv,D) -> (B,S,Hq,D) by repeating kv heads (GQA)."""
    return jnp.repeat(k, group, axis=2) if group > 1 else k


def cache_axes(n_kv_heads: int) -> tuple:
    """Logical axes for a (B, Hkv, Sc, D) KV-cache entry: heads over "model"
    when divisible, else the cache sequence dim (matches
    runtime.sharding._axes_for_state so prefill output needs no reshard)."""
    from repro.runtime.pspec import current_rules
    rules = current_rules()
    if rules is None:
        return ("batch", "kv_heads", None, None)
    msize = rules.mesh.shape.get("model", 1)
    if n_kv_heads % msize == 0:
        return ("batch", "kv_heads", None, None)
    return ("batch", None, "kv_seq", None)


def to_cache(x: Array, capacity: int) -> Array:
    """A prefill's (B, S, Hkv, D) keys or values as a head-major cache
    entry (B, Hkv, capacity, D), zero past S."""
    x = jnp.pad(x, ((0, 0), (0, capacity - x.shape[1]), (0, 0), (0, 0)))
    x = x.transpose(0, 2, 1, 3)
    return constrain(x, *cache_axes(x.shape[1]))


def _score_axes(n_heads: int) -> tuple:
    """Sharding for (B,H,Sq,...) score-like tensors: heads over "model" when
    divisible, else sequence-parallel on Sq.  Falls back to no-op without an
    active mesh."""
    from repro.runtime.pspec import current_rules
    rules = current_rules()
    if rules is None:
        return ("batch", "heads", None)
    msize = rules.mesh.shape.get("model", 1)
    if n_heads % msize == 0:
        return ("batch", "heads", None)
    return ("batch", None, "seq_sp")


# ---------------------------------------------------------------------------
# naive full attention (reference)
# ---------------------------------------------------------------------------


def attend_naive(q: Array, k: Array, v: Array, pos_q: Array, pos_k: Array,
                 causal: bool, window: int, plan: ExecPlan,
                 scale: Optional[float] = None) -> Array:
    b, sq, hq, hd = q.shape
    nkv = k.shape[2]
    ax = _score_axes(hq)
    # (B,H,S,D) layout; kv heads repeated for GQA.  Scores shard over heads
    # (TP-natural) or the q-seq dim — never replicated (on real TPU the
    # Pallas flash kernel removes the score tensor entirely).
    qh = constrain(q.transpose(0, 2, 1, 3), ax[0], ax[1], ax[2], None)
    kh = _repeat_kv(k, hq // nkv).transpose(0, 2, 1, 3)
    vh = _repeat_kv(v, hq // nkv).transpose(0, 2, 1, 3)
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32) * scale
    scores = constrain(scores, ax[0], ax[1], ax[2], None)
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= pos_k[None, :] <= pos_q[:, None]
    if window > 0:
        mask &= pos_k[None, :] > pos_q[:, None] - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(L.cdtype(plan))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention — the offloaded path
#
# ``_flash`` is a custom_vjp: plain autodiff through the online-softmax scan
# stacks the per-chunk (Sq, ck) score tensors as saved residuals (measured:
# 2.7 GB/layer + replication all-gathers at train_4k), defeating the whole
# point.  The custom backward recomputes probabilities chunk-by-chunk from
# the saved (q, k, v, out, logsumexp) — exactly the Pallas kernel's backward.
# ---------------------------------------------------------------------------


def _flash_mask(pos_q, pos_k, causal: bool, window: int, sk_valid: int):
    mask = pos_k[None, :] < sk_valid          # padded keys masked out
    if causal:
        mask &= pos_k[None, :] <= pos_q[:, None]
    if window > 0:
        mask &= pos_k[None, :] > pos_q[:, None] - window
    return mask


def _chunk_kv(x: Array, ck: int) -> Array:
    bh, sk, d = x.shape
    return x.reshape(bh, sk // ck, ck, d).transpose(1, 0, 2, 3)   # (n,BH,ck,D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q: Array, k: Array, v: Array, causal: bool, window: int,
           ck: int, out_dtype, sk_valid: int, scale: float,
           q_offset: int) -> Array:
    """Flattened-head flash attention.  q: (BH, Sq, D); k: (BH, Sk, D);
    v: (BH, Sk, Dv) (equal heads — GQA repeat outside).  Sk must be a
    multiple of ck (padded by the caller; sk_valid = true length); query i
    sits at position ``q_offset + i``.  Runs LOCALLY under shard_map — no
    sharding constraints inside."""
    out, _ = _flash_fwd(q, k, v, causal, window, ck, out_dtype, sk_valid,
                        scale, q_offset)
    return out


def _positions(sq: int, sk: int, q_offset: int):
    pos_q = jnp.arange(sq, dtype=jnp.int32)
    if q_offset:
        pos_q = pos_q + q_offset
    return pos_q, jnp.arange(sk, dtype=jnp.int32)


def _flash_fwd(q, k, v, causal, window, ck, out_dtype, sk_valid, scale,
               q_offset):
    bh, sq, hd = q.shape
    sk, vd = k.shape[1], v.shape[-1]
    pos_q, pos_k = _positions(sq, sk, q_offset)
    kc, vc = _chunk_kv(k, ck), _chunk_kv(v, ck)
    pkc = pos_k.reshape(-1, ck)

    def body(carry, chunk):
        m, l, acc = carry
        k_j, v_j, pk_j = chunk                                    # (BH,ck,D)
        s = jnp.einsum("bqd,bkd->bqk", q, k_j,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(_flash_mask(pos_q, pk_j, causal, window, sk_valid)[None],
                      s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqk,bkd->bqd", p.astype(k_j.dtype), v_j)
        acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((bh, sq), NEG_INF, jnp.float32),
            jnp.zeros((bh, sq), jnp.float32),
            jnp.zeros((bh, sq, vd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, pkc))
    out = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(out_dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-37))
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, ck, out_dtype, sk_valid, scale, q_offset,
               res, dout):
    q, k, v, out, lse = res
    bh, sq, hd = q.shape
    sk, vd = k.shape[1], v.shape[-1]
    pos_q, pos_k = _positions(sq, sk, q_offset)
    kc, vc = _chunk_kv(k, ck), _chunk_kv(v, ck)
    pkc = pos_k.reshape(-1, ck)
    do = dout.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)        # (BH,Sq)

    def body(dq, chunk):
        k_j, v_j, pk_j = chunk
        s = jnp.einsum("bqd,bkd->bqk", q, k_j,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(_flash_mask(pos_q, pk_j, causal, window, sk_valid)[None],
                      s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                           # (BH,Sq,ck)
        dv_j = jnp.einsum("bqk,bqd->bkd", p, do)
        dp = jnp.einsum("bqd,bkd->bqk", do, v_j.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, k_j.astype(jnp.float32))
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((bh, sq, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (kc, vc, pkc))
    dk = dks.transpose(1, 0, 2, 3).reshape(bh, sk, hd)
    dv = dvs.transpose(1, 0, 2, 3).reshape(bh, sk, vd)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _bh_axes(bh: int) -> tuple:
    """Longest mesh-axis tuple dividing the flattened (B*H) dim."""
    from repro.runtime.pspec import current_rules
    rules = current_rules()
    if rules is None:
        return ()
    mesh = rules.mesh
    for cand in (("pod", "data", "model"), ("data", "model"), ("pod", "data"),
                 ("data",), ("model",)):
        axes = tuple(a for a in cand if a in mesh.shape)
        if not axes or axes != tuple(cand[-len(axes):]) and axes != tuple(cand):
            pass
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if axes and bh % size == 0:
            return axes
    return ()


#: query rows per block of a causal self-attention longer than this: a block
#: attends only the keys up to its last row, so blocks above the diagonal are
#: skipped and the (BH, rows, kv chunk) score tile stays bounded
Q_BLOCK = 4096


def _flash_rows(q, k, v, causal, window, ck, out_dtype, sk_valid, scale):
    """``_flash`` over all rows at once, or, for a causal self-attention
    longer than ``Q_BLOCK``, block by block of query rows, each against the
    key prefix it can see."""
    sq = q.shape[1]
    if not causal or sq != sk_valid or sq <= Q_BLOCK:
        return _flash(q, k, v, causal, window, ck, out_dtype, sk_valid, scale,
                      0)
    outs = []
    for s0 in range(0, sq, Q_BLOCK):
        s1 = min(s0 + Q_BLOCK, sq)
        end = -(-s1 // ck) * ck
        outs.append(_flash(q[:, s0:s1], k[:, :end], v[:, :end], causal,
                           window, ck, out_dtype, s1, scale, s0))
    return jnp.concatenate(outs, axis=1)


def attend_chunked(q: Array, k: Array, v: Array, pos_q: Array, pos_k: Array,
                   causal: bool, window: int, plan: ExecPlan,
                   scale: Optional[float] = None) -> Array:
    """Flash attention over KV chunks with a custom backward (recompute, no
    stacked score residuals).  The (B, H) dims flatten into one leading dim
    sharded across the whole mesh with shard_map: compute is fully local —
    zero collectives inside attention.  jnp twin of kernels/flash_attention.
    Positions must be aranges (true for every full-sequence caller).  v may
    be narrower than q and k (latent attention); ``scale`` defaults to
    1/sqrt(head dim)."""
    from jax.sharding import PartitionSpec as P
    from repro.runtime.pspec import axis_rules, current_rules

    b, sq, hq, hd = q.shape
    dv = v.shape[-1]
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    sk = k.shape[1]
    nkv = k.shape[2]
    group = hq // nkv
    ck = min(plan.attn_kv_chunk, sk)
    pad = (-sk) % ck
    kh = _repeat_kv(k, group)                     # (B,Sk,H,D); grad sums groups
    vh = _repeat_kv(v, group)
    if pad:
        kh = jnp.pad(kh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # anchor the (B,S,H,D) <-> (BH,S,D) transitions on the TP-natural head
    # sharding so the boundary reshards are local relayouts, not gathers
    hax = _score_axes(hq)[1]  # "heads" when divisible, else None
    q = constrain(q, "batch", None, hax, None)
    kh = constrain(kh, "batch", None, hax, None)
    vh = constrain(vh, "batch", None, hax, None)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, hd)
    kf = kh.transpose(0, 2, 1, 3).reshape(b * hq, -1, hd)
    vf = vh.transpose(0, 2, 1, 3).reshape(b * hq, -1, dv)

    rules = current_rules()
    bh = b * hq
    axes = _bh_axes(bh)
    # non-divisible (B*H) (e.g. 20 heads on a 16-way axis) would fall back to
    # partial sharding and replicate score rows 16x — pad BH to the full mesh
    # instead (zero rows cost nothing; outputs sliced away)
    pad_bh = 0
    if rules is not None:
        full = tuple(a for a in ("pod", "data", "model") if a in rules.mesh.shape)
        fsize = 1
        for a in full:
            fsize *= rules.mesh.shape[a]
        cur = 1
        for a in axes:
            cur *= rules.mesh.shape[a]
        if fsize > cur:
            pad_bh = (-bh) % fsize
            axes = full
    if pad_bh:
        qf = jnp.pad(qf, ((0, pad_bh), (0, 0), (0, 0)))
        kf = jnp.pad(kf, ((0, pad_bh), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, pad_bh), (0, 0), (0, 0)))
    if rules is None or not axes:
        out = _flash_rows(qf, kf, vf, causal, window, ck, L.cdtype(plan), sk,
                          scale)
    else:
        spec = P(axes if len(axes) > 1 else axes[0], None, None)

        def inner(qi, ki, vi):
            with axis_rules(None):
                return _flash_rows(qi, ki, vi, causal, window, ck,
                                   L.cdtype(plan), sk, scale)

        out = jax.shard_map(inner, mesh=rules.mesh,
                            in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)(qf, kf, vf)
    if pad_bh:
        out = out[:bh]
    out = out.reshape(b, hq, sq, dv).transpose(0, 2, 1, 3)
    return constrain(out, "batch", None, hax, None)


# ---------------------------------------------------------------------------
# banded local attention (sub-quadratic; always used for attn_kind=local when
# the sequence is longer than the window)
# ---------------------------------------------------------------------------


def attend_local_banded(q: Array, k: Array, v: Array, pos_q: Array, pos_k: Array,
                        window: int, plan: ExecPlan) -> Array:
    """Each q chunk (size w) attends its own + previous kv chunk only.

    Exact for causal local attention with window <= chunk size: query at
    position p sees (p - w, p].  FLOPs: 2*w per query — sub-quadratic.
    """
    b, sq, hq, hd = q.shape
    nkv = k.shape[2]
    w = window
    if sq % w != 0 or k.shape[1] != sq:
        # fallback (ragged tails handled by the generic chunked path)
        return attend_chunked(q, k, v, pos_q, pos_k, True, window, plan)
    n = sq // w
    qc = _group(q, nkv).reshape(b, n, w, nkv, hq // nkv, hd)
    qc = constrain(qc, "batch", "seq_sp", None, None, None, None)  # SP chunks
    kc = k.reshape(b, n, w, nkv, hd)
    vc = v.reshape(b, n, w, nkv, hd)
    k_prev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    v_prev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    kk = jnp.concatenate([k_prev, kc], axis=2)  # (B,n,2w,Hkv,D)
    vv = jnp.concatenate([v_prev, vc], axis=2)
    pq = pos_q.reshape(n, w)
    pk = pos_k.reshape(n, w)
    pk_prev = jnp.concatenate(
        [jnp.full_like(pk[:1], np.iinfo(np.int32).max), pk[:-1]], axis=0)
    pkk = jnp.concatenate([pk_prev, pk], axis=1)  # (n, 2w)

    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qc, kk,
                   preferred_element_type=jnp.float32) * scale
    mask = (pkk[:, None, :] <= pq[:, :, None]) & (pkk[:, None, :] > pq[:, :, None] - w)
    s = jnp.where(mask[None, :, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(L.cdtype(plan))
    out = jnp.einsum("bnhgqk,bnkhd->bnqhgd", p, vv)
    return out.reshape(b, sq, hq, hd)


# ---------------------------------------------------------------------------
# decode (single new token against a cache)
# ---------------------------------------------------------------------------


def decode_valid(sc: int, cache_len: Array, ring: bool) -> Array:
    """(Sc,) mask of the cache slots a new token at position ``cache_len``
    attends besides itself.  A ring (local attention, Sc = window) holds the
    min(cache_len, Sc - 1) most recent tokens, never the slot the new token
    overwrites; a linear cache holds ``cache_len`` tokens."""
    idx = jnp.arange(sc)
    if ring:
        return (cache_len - 1 - idx) % sc < jnp.minimum(cache_len, sc - 1)
    return idx < cache_len


def attend_decode(q1: Array, cache: KVCache, valid: Optional[Array],
                  plan: ExecPlan, k1: Optional[Array] = None,
                  v1: Optional[Array] = None) -> Array:
    """One query token against a head-major cache that it only reads.

    q1: (B,1,Hq,D); cache.k/v: (B,Hkv,Sc,D); ``valid``: (Sc,) mask, or None
    for every slot.  The new token's own k1/v1 (B,1,Hkv,D), when given, join
    the same softmax as one more key: scores over the cache and against k1,
    softmax, cast, then ``p_cache @ V_cache + p_new * v1``.  Returns
    (B,1,Hq,D)."""
    b, _, hq, hd = q1.shape
    nkv = cache.k.shape[1]
    qg = _group(q1, nkv)[:, 0]  # (B,Hkv,G,D)
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, cache.k,
                   preferred_element_type=jnp.float32) * scale
    if valid is not None:
        s = jnp.where(valid[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if k1 is not None:
        s_new = jnp.einsum("bhgd,bhd->bhg", qg, k1[:, 0],
                           preferred_element_type=jnp.float32)[..., None] * scale
        m = jnp.maximum(m, s_new)
    e = jnp.exp(s - m)
    total = jnp.sum(e, axis=-1, keepdims=True)
    if k1 is not None:
        e_new = jnp.exp(s_new - m)
        total = total + e_new
    dt = L.cdtype(plan)
    out = jnp.einsum("bhgk,bhkd->bhgd", (e / total).astype(dt), cache.v,
                     preferred_element_type=jnp.float32)
    if v1 is not None:
        p_new = (e_new / total).astype(dt).astype(jnp.float32)
        out = out + p_new * v1[:, 0, :, None].astype(jnp.float32)
    return out.astype(dt).reshape(b, 1, hq, hd)


def write_tokens(stack: dict, new: dict, cache_len: Array, ring: bool) -> dict:
    """Write one token's k/v per layer into the stacked caches in place.

    ``stack``: name -> (L,B,Hkv,Sc,D), or a latent (L,B,Sc,R); ``new``:
    name -> the same with Sc = 1, as a decode scan emits them.  The slot,
    on the second-to-last axis, is ``cache_len``, or ``cache_len % Sc`` for
    a ring.  One dynamic_update_slice per leaf: on a donated state it
    touches only the token's slot."""
    with jax.named_scope("kv_cache"):
        out = {}
        for name, x in new.items():
            axis = x.ndim - 2
            sc = stack[name].shape[axis]
            slot = (cache_len % sc) if ring else cache_len
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                stack[name], x, slot, axis=axis)
        return out


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2 §2.1, no q LoRA)
#
#   q = h Wq -> per head [q_nope | q_pe];  [c | k_pe] = h Wkv_a;
#   c = RMSNorm(c);  [k_nope | v] = c Wkv_b per head;  rope on q_pe, k_pe
#   (k_pe one head shared by all);  softmax(q.k * scale) v;  Wo.
#
# Prefill runs the expanded form: per-head k = [k_nope | k_pe] and v built
# from the latent, then ordinary causal attention (q/k dim nope + rope, v dim
# v_head_dim).  Decode runs the absorbed form against a cache of c and k_pe
# only: q_nope goes through Wkv_b's k half into the latent, scores are
# q_lat.c + q_pe.k_pe, the weighted sum stays in the latent and Wkv_b's v
# half and Wo follow.  No per-head k/v is ever built over the cache.
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], (d, h * (dn + dr)), dtype=dtype),
        "wkv_a": L.dense_init(ks[1], (d, r + dr), dtype=dtype),
        "kv_norm": jnp.zeros((r,), dtype),
        "wkv_b": L.dense_init(ks[2], (r, h * (dn + dv)), dtype=dtype),
        "wo": L.dense_init(ks[3], (h * dv, d), dtype=dtype),
    }


def mla_scale(cfg: ArchConfig) -> float:
    """Softmax scale: (nope + rope)^-1/2, times YaRN's mscale squared."""
    y = cfg.rope_yarn
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 \
        * L.yarn_mscale(y.factor, y.mscale) ** 2


def _mla_rope(x: Array, positions: Array, cfg: ArchConfig) -> Array:
    return L.apply_rope_pairs(
        x, positions,
        L.yarn_inv_freq(x.shape[-1], cfg.rope_theta, cfg.rope_yarn))


def mla_project(x: Array, p: dict, cfg: ArchConfig, plan: ExecPlan,
                positions: Array) -> tuple:
    """(q_nope (B,S,H,dn), q_pe (B,S,H,dr), c (B,S,R), k_pe (B,S,dr)): the
    queries, and what the cache keeps, normalised and rotated.  Two matmuls,
    or one over [Wq | Wkv_a] (qkv_fused)."""
    dt = L.cdtype(plan)
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if plan.qkv_fused:
        w = jnp.concatenate([p["wq"], p["wkv_a"]], axis=1).astype(dt)
        q, kv = jnp.split(x @ w, [h * (dn + dr)], axis=-1)
    else:
        q, kv = x @ p["wq"].astype(dt), x @ p["wkv_a"].astype(dt)
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], _mla_rope(q[..., dn:], positions, cfg)
    c = L.rmsnorm(kv[..., :r], p["kv_norm"], cfg.norm_eps, plan)
    k_pe = _mla_rope(kv[..., None, r:], positions, cfg)[:, :, 0]
    return q_nope, q_pe, c, k_pe


def _kv_b(p: dict, cfg: ArchConfig, dt) -> tuple[Array, Array]:
    """Wkv_b as (R, H, dn) for keys and (R, H, dv) for values."""
    w = p["wkv_b"].astype(dt).reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_prefill(x: Array, p: dict, cfg: ArchConfig, plan: ExecPlan,
                positions: Array) -> tuple[Array, Array, Array]:
    """Expanded form over a full sequence.  Returns the attention output
    (B,S,H*dv), before Wo, and the latent c, k_pe the cache keeps."""
    dt = L.cdtype(plan)
    b, s, _ = x.shape
    q_nope, q_pe, c, k_pe = mla_project(x, p, cfg, plan, positions)
    w_k, w_v = _kv_b(p, cfg, dt)
    k_nope = jnp.einsum("bsr,rhn->bshn", c, w_k)
    v = jnp.einsum("bsr,rhv->bshv", c, w_v)
    k_pe_h = jnp.broadcast_to(k_pe[:, :, None], q_pe.shape)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe_h], axis=-1)
    o = attend(q, k, v, positions, positions, causal=True, attn_kind="full",
               window=0, plan=plan, scale=mla_scale(cfg))
    return o.reshape(b, s, -1), c, k_pe


def mla_decode(x1: Array, p: dict, cfg: ArchConfig, plan: ExecPlan,
               cache_c: Array, cache_pe: Array, valid: Array,
               pos: Array) -> tuple[Array, Array, Array]:
    """Absorbed form: one token (B,1,d) against the latent cache it only
    reads, c (B,Sc,R) and k_pe (B,Sc,dr), with ``valid`` (Sc,) slots; the
    token's own latent joins the softmax as one more key.  Returns the
    attention output (B,1,H*dv), before Wo, and the token's c (B,1,R) and
    k_pe (B,1,dr)."""
    dt = L.cdtype(plan)
    f32 = jnp.float32
    b = x1.shape[0]
    q_nope, q_pe, c1, pe1 = mla_project(x1, p, cfg, plan, pos)
    w_k, w_v = _kv_b(p, cfg, dt)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_k,
                       preferred_element_type=f32).astype(dt)
    q_pe = q_pe[:, 0]
    scale = mla_scale(cfg)
    s = (jnp.einsum("bhr,bkr->bhk", q_lat, cache_c, preferred_element_type=f32)
         + jnp.einsum("bhe,bke->bhk", q_pe, cache_pe,
                      preferred_element_type=f32)) * scale
    s = jnp.where(valid[None, None], s, NEG_INF)
    s_new = (jnp.einsum("bhr,br->bh", q_lat, c1[:, 0],
                        preferred_element_type=f32)
             + jnp.einsum("bhe,be->bh", q_pe, pe1[:, 0],
                          preferred_element_type=f32))[..., None] * scale
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_new)
    e, e_new = jnp.exp(s - m), jnp.exp(s_new - m)
    total = jnp.sum(e, axis=-1, keepdims=True) + e_new
    o_lat = jnp.einsum("bhk,bkr->bhr", (e / total).astype(dt), cache_c,
                       preferred_element_type=f32)
    p_new = (e_new / total).astype(dt).astype(f32)
    o_lat = o_lat + p_new * c1[:, 0, None, :].astype(f32)
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(dt), w_v,
                   preferred_element_type=f32).astype(dt)
    return o.reshape(b, 1, -1), c1, pe1


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------


def attend(q: Array, k: Array, v: Array, pos_q: Array, pos_k: Array, *,
           causal: bool, attn_kind: str, window: int, plan: ExecPlan,
           scale: Optional[float] = None) -> Array:
    if attn_kind == "local" and causal and q.shape[1] > window:
        return attend_local_banded(q, k, v, pos_q, pos_k, window, plan)
    win = window if attn_kind == "local" else 0
    if plan.attn_impl == "chunked":
        return attend_chunked(q, k, v, pos_q, pos_k, causal, win, plan, scale)
    return attend_naive(q, k, v, pos_q, pos_k, causal, win, plan, scale)
