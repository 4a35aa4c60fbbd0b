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
    k: Array  # (B, S_cache, Hkv, D)
    v: Array  # (B, S_cache, Hkv, D)


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
    """Logical axes for a (B, Sc, Hkv, D) KV-cache entry: heads over "model"
    when divisible, else the cache sequence dim (matches
    runtime.sharding._axes_for_state so prefill output needs no reshard)."""
    from repro.runtime.pspec import current_rules
    rules = current_rules()
    if rules is None:
        return ("batch", None, "kv_heads", None)
    msize = rules.mesh.shape.get("model", 1)
    if n_kv_heads % msize == 0:
        return ("batch", None, "kv_heads", None)
    return ("batch", "kv_seq", None, None)


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
                 causal: bool, window: int, plan: ExecPlan) -> Array:
    b, sq, hq, hd = q.shape
    nkv = k.shape[2]
    ax = _score_axes(hq)
    # (B,H,S,D) layout; kv heads repeated for GQA.  Scores shard over heads
    # (TP-natural) or the q-seq dim — never replicated (on real TPU the
    # Pallas flash kernel removes the score tensor entirely).
    qh = constrain(q.transpose(0, 2, 1, 3), ax[0], ax[1], ax[2], None)
    kh = _repeat_kv(k, hq // nkv).transpose(0, 2, 1, 3)
    vh = _repeat_kv(v, hq // nkv).transpose(0, 2, 1, 3)
    scale = 1.0 / np.sqrt(hd)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q: Array, k: Array, v: Array, causal: bool, window: int,
           ck: int, out_dtype, sk_valid: int) -> Array:
    """Flattened-head flash attention.  q: (BH, Sq, D); k/v: (BH, Sk, D)
    (equal heads — GQA repeat outside).  Sk must be a multiple of ck (padded
    by the caller; sk_valid = true length).  Runs LOCALLY under shard_map —
    no sharding constraints inside."""
    out, _ = _flash_fwd(q, k, v, causal, window, ck, out_dtype, sk_valid)
    return out


def _flash_fwd(q, k, v, causal, window, ck, out_dtype, sk_valid):
    bh, sq, hd = q.shape
    sk = k.shape[1]
    pos_q = jnp.arange(sq, dtype=jnp.int32)
    pos_k = jnp.arange(sk, dtype=jnp.int32)
    kc, vc = _chunk_kv(k, ck), _chunk_kv(v, ck)
    pkc = pos_k.reshape(-1, ck)
    scale = 1.0 / np.sqrt(hd)

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
            jnp.zeros((bh, sq, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, pkc))
    out = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(out_dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-37))
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, ck, out_dtype, sk_valid, res, dout):
    q, k, v, out, lse = res
    bh, sq, hd = q.shape
    sk = k.shape[1]
    pos_q = jnp.arange(sq, dtype=jnp.int32)
    pos_k = jnp.arange(sk, dtype=jnp.int32)
    kc, vc = _chunk_kv(k, ck), _chunk_kv(v, ck)
    pkc = pos_k.reshape(-1, ck)
    scale = 1.0 / np.sqrt(hd)
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
    dv = dvs.transpose(1, 0, 2, 3).reshape(bh, sk, hd)
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


def attend_chunked(q: Array, k: Array, v: Array, pos_q: Array, pos_k: Array,
                   causal: bool, window: int, plan: ExecPlan) -> Array:
    """Flash attention over KV chunks with a custom backward (recompute, no
    stacked score residuals).  The (B, H) dims flatten into one leading dim
    sharded across the whole mesh with shard_map: compute is fully local —
    zero collectives inside attention.  jnp twin of kernels/flash_attention.
    Positions must be aranges (true for every full-sequence caller)."""
    from jax.sharding import PartitionSpec as P
    from repro.runtime.pspec import axis_rules, current_rules

    b, sq, hq, hd = q.shape
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
    vf = vh.transpose(0, 2, 1, 3).reshape(b * hq, -1, hd)

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
        out = _flash(qf, kf, vf, causal, window, ck, L.cdtype(plan), sk)
    else:
        spec = P(axes if len(axes) > 1 else axes[0], None, None)

        def inner(qi, ki, vi):
            with axis_rules(None):
                return _flash(qi, ki, vi, causal, window, ck, L.cdtype(plan), sk)

        out = jax.shard_map(inner, mesh=rules.mesh,
                            in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)(qf, kf, vf)
    if pad_bh:
        out = out[:bh]
    out = out.reshape(b, hq, sq, hd).transpose(0, 2, 1, 3)
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


def attend_decode(q1: Array, cache: KVCache, cache_len: Array,
                  window: int, plan: ExecPlan, ring: bool) -> Array:
    """q1: (B,1,Hq,D); cache.k/v: (B,Sc,Hkv,D).  Returns (B,1,Hq,D).

    ``ring`` means the cache is a ring buffer of size `window` (local attn);
    otherwise it is a linear buffer with `cache_len` valid entries.
    """
    b, _, hq, hd = q1.shape
    sc, nkv = cache.k.shape[1], cache.k.shape[2]
    qg = _group(q1, nkv)[:, 0]  # (B,Hkv,G,D)
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, cache.k,
                   preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(sc)
    if ring:
        # valid entries: the min(cache_len, window) most recent slots
        age = (cache_len - 1 - idx) % sc  # 0 = newest
        valid = age < jnp.minimum(cache_len, sc)
    else:
        valid = idx < cache_len
        if window > 0:
            valid &= idx > cache_len - 1 - window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(L.cdtype(plan))
    out = jnp.einsum("bhgk,bkhd->bhgd", p, cache.v)
    return out.reshape(b, 1, hq, hd)


def cache_update(cache: KVCache, k1: Array, v1: Array, cache_len: Array,
                 ring: bool) -> KVCache:
    """Insert one token's k/v at the right slot (ring or linear)."""
    sc = cache.k.shape[1]
    slot = (cache_len % sc) if ring else cache_len
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k1, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v1, slot, axis=1)
    return KVCache(k, v)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------


def attend(q: Array, k: Array, v: Array, pos_q: Array, pos_k: Array, *,
           causal: bool, attn_kind: str, window: int, plan: ExecPlan) -> Array:
    if attn_kind == "local" and causal and q.shape[1] > window:
        return attend_local_banded(q, k, v, pos_q, pos_k, window, plan)
    win = window if attn_kind == "local" else 0
    if plan.attn_impl == "chunked":
        return attend_chunked(q, k, v, pos_q, pos_k, causal, win, plan)
    return attend_naive(q, k, v, pos_q, pos_k, causal, win, plan)
