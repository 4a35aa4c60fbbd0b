"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _arr(rng, *shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (1, 128, 2, 2, 32),
    (2, 256, 4, 2, 64),
    (1, 192, 8, 1, 16),    # MQA, ragged vs block
    (2, 64, 4, 4, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, b, s, hq, hkv, d, causal, dtype):
    if dtype == jnp.bfloat16 and d > 64:
        pytest.skip("loose-tolerance case covered at d<=64")
    q = _arr(rng, b, s, hq, d, dtype=dtype)
    k = _arr(rng, b, s, hkv, d, dtype=dtype)
    v = _arr(rng, b, s, hkv, d, dtype=dtype)
    blk = 64
    out = ops.flash_attention(q, k, v, causal=causal, blk_q=blk, blk_k=blk)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    exp = ref.flash_attention_ref(
        qf, kf, vf, causal=causal, scale=1 / np.sqrt(d), group=hq // hkv)
    exp = exp.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# rglru linear recurrence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,d,chunk,dblk", [
    (1, 128, 128, 64, 128),
    (2, 256, 256, 128, 128),
    (2, 100, 128, 64, 128),   # ragged seq (padding path)
    (1, 64, 384, 32, 128),
])
def test_rglru_sweep(rng, b, s, d, chunk, dblk):
    log_a = -jnp.abs(_arr(rng, b, s, d)) * 0.2
    bb = _arr(rng, b, s, d, scale=0.5)
    out = ops.rglru_scan(log_a, bb, chunk=chunk, d_block=dblk)
    exp = ref.rglru_scan_ref(log_a, bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-4)


def test_rglru_initial_state(rng):
    log_a = -jnp.abs(_arr(rng, 2, 64, 128)) * 0.2
    bb = _arr(rng, 2, 64, 128, scale=0.5)
    h0 = _arr(rng, 2, 128)
    out = ops.rglru_scan(log_a, bb, h0, chunk=32)
    # oracle: fold h0 into b[0]
    bb2 = bb.at[:, 0].add(jnp.exp(log_a[:, 0]) * h0)
    exp = ref.rglru_scan_ref(log_a, bb2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,h,d,chunk", [
    (1, 64, 2, 64, 16),
    (2, 128, 2, 64, 32),
    (1, 96, 4, 32, 64),    # chunk > s/1 with ragged padding
])
def test_wkv6_sweep(rng, b, s, h, d, chunk):
    r = _arr(rng, b, s, h, d, scale=0.5)
    k = _arr(rng, b, s, h, d, scale=0.5)
    v = _arr(rng, b, s, h, d, scale=0.5)
    lw = -jnp.abs(_arr(rng, b, s, h, d)) * 0.3
    u = jnp.asarray(rng.normal(size=(h, d)) * 0.1, jnp.float32)
    out = ops.wkv6(r, k, v, lw, u, chunk=chunk)
    rf = r.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    lwf = lw.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    uf = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, 1, d)
    exp = ref.wkv6_ref(rf, kf, vf, lwf, uf).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=1e-3)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d", [(64, 128), (100, 256), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rng, n, d, dtype):
    x = _arr(rng, n, d, dtype=dtype)
    s = _arr(rng, d, scale=0.1)
    out = ops.rmsnorm(x, s)
    exp = ref.rmsnorm_ref(x, s)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# model-level flash (attend_chunked custom_vjp) vs naive — values AND grads
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
def test_model_flash_custom_vjp_matches_naive(rng, causal):
    from repro.models import attention as A
    from repro.models.plan import ExecPlan
    B, S, Hq, Hkv, D = 2, 96, 4, 2, 16
    q = _arr(rng, B, S, Hq, D)
    k = _arr(rng, B, S, Hkv, D)
    v = _arr(rng, B, S, Hkv, D)
    pos = jnp.arange(S)
    plan = ExecPlan(attn_kv_chunk=32, compute_dtype="float32")

    def loss_naive(q, k, v):
        return jnp.sum(jnp.sin(A.attend_naive(q, k, v, pos, pos, causal, 0, plan)))

    def loss_chunk(q, k, v):
        return jnp.sum(jnp.sin(A.attend_chunked(q, k, v, pos, pos, causal, 0, plan)))

    o1, g1 = jax.value_and_grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    o2, g2 = jax.value_and_grad(loss_chunk, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(o1 - o2)) < 1e-3
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# kernel names (what a profiler trace and the compiled custom call show)
# ---------------------------------------------------------------------------


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                out += _pallas_names(inner)
    return out


@pytest.mark.parametrize("kernel", ["flash_attention", "rglru_scan", "wkv6",
                                    "rmsnorm"])
def test_pallas_calls_carry_their_kernel_names(kernel):
    x = jnp.ones((2, 64, 2, 32), jnp.float32)
    calls = {
        "flash_attention": lambda: ops.flash_attention(x, x, x),
        "rglru_scan": lambda: ops.rglru_scan(x[:, :, 0], x[:, :, 0]),
        "wkv6": lambda: ops.wkv6(x, x, x, -x, x[0, 0]),
        "rmsnorm": lambda: ops.rmsnorm(x, x[0, 0, 0]),
    }
    assert _pallas_names(jax.make_jaxpr(calls[kernel])().jaxpr) == [kernel]
