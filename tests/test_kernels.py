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


# The widened kernel at latent attention's shapes (q/k 192, v 128, MLA's
# scale), causal over lengths that are not block multiples: against the jnp
# twin it stands in for (``_flash``, same bf16-in / f32-accumulate
# arithmetic) and the materialised reference, and its custom_vjp gradient
# against ``_flash``'s.


def _mla_qkv(rng, s, dtype, bh=2):
    return (_arr(rng, bh, s, 192, dtype=dtype), _arr(rng, bh, s, 192, dtype=dtype),
            _arr(rng, bh, s, 128, dtype=dtype))


def _mla_scale():
    from repro.configs import get_config
    from repro.models import attention as A
    return A.mla_scale(get_config("deepseek_v2_lite"))


@pytest.mark.parametrize("s", [1000, 1536])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_mla_dims_match_flash_and_naive(rng, s, dtype):
    from repro.kernels import flash_attention as fa
    from repro.models import attention as A
    from repro.models.plan import ExecPlan
    q, k, v = _mla_qkv(rng, s, dtype)
    scale = _mla_scale()
    out = fa.flash_attention_bh(q, k, v, causal=True, scale=scale, blk_q=512,
                                blk_k=256, interpret=True)
    assert out.shape == (2, s, 128) and out.dtype == dtype
    twin = A._flash(q, k, v, True, 0, s if s % 256 else 256, dtype, s, scale, 0)
    pos = jnp.arange(s)
    plan = ExecPlan(compute_dtype=jnp.dtype(dtype).name)
    naive = A.attend_naive(q[None].transpose(0, 2, 1, 3),
                           k[None].transpose(0, 2, 1, 3),
                           v[None].transpose(0, 2, 1, 3), pos, pos, True, 0,
                           plan, scale)[0].transpose(1, 0, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for exp in (twin, naive):
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32), atol=tol,
                                   rtol=tol)


@pytest.mark.parametrize("s", [1000, 1536])
def test_flash_kernel_log_sum_exp_and_gradient_match_flash(rng, s):
    from repro.models import attention as A
    q, k, v = _mla_qkv(rng, s, jnp.float32)
    scale, ck = _mla_scale(), 256
    pad = (-s) % ck
    kp, vp = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (k, v))

    def loss(attend):
        return lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v)))

    def kernel(q, k, v):
        return A._flash_pallas(q, k, v, ck, scale, True)

    def twin(q, k, v):
        return A._flash(q, k, v, True, 0, ck, jnp.float32, s, scale, 0)

    _, (*_, lse) = A._flash_pallas_fwd(q, kp, vp, ck, scale, True)
    _, (*_, lse_twin) = A._flash_fwd(q, kp, vp, True, 0, ck, jnp.float32, s,
                                     scale, 0)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_twin),
                               atol=1e-5, rtol=1e-5)
    o1, g1 = jax.value_and_grad(loss(kernel), argnums=(0, 1, 2))(q, kp, vp)
    o2, g2 = jax.value_and_grad(loss(twin), argnums=(0, 1, 2))(q, kp, vp)
    np.testing.assert_allclose(float(o1), float(o2), rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-3)


def test_long_causal_attention_keeps_the_row_block_loop_off_tpu(rng):
    """Lowered for the CPU, a causal self-attention just above ``Q_BLOCK``
    rows runs ``_flash`` block by block of rows, as before the kernel took
    the TPU branch: no Pallas call is lowered, and the result is the loop's
    to the bit."""
    from repro.models import attention as A
    from repro.models.plan import ExecPlan
    s, h, d = A.Q_BLOCK + 200, 1, 16
    q, k, v = (_arr(rng, 1, s, h, d) for _ in range(3))
    pos = jnp.arange(s)
    plan = ExecPlan(compute_dtype="float32")
    fn = jax.jit(lambda q, k, v: A.attend_chunked(q, k, v, pos, pos, True, 0,
                                                   plan))
    text = fn.lower(q, k, v).as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text.lower()
    out = fn(q, k, v)
    ck = plan.attn_kv_chunk
    end = -(-s // ck) * ck
    qf, kf, vf = (x[0].transpose(1, 0, 2) for x in (q, k, v))
    kf, vf = (jnp.pad(x, ((0, 0), (0, end - s), (0, 0))) for x in (kf, vf))
    loop = jnp.concatenate(
        [A._flash(qf[:, s0:s1], kf[:, :e], vf[:, :e], True, 0, ck,
                  jnp.float32, s1, d ** -0.5, s0)
         for s0, s1, e in A._row_blocks(s, ck)], axis=1)
    np.testing.assert_array_equal(np.asarray(out[0].transpose(1, 0, 2)),
                                  np.asarray(loop))
    naive = A.attend_naive(q, k, v, pos, pos, True, 0, plan)
    np.testing.assert_allclose(np.asarray(out), np.asarray(naive), atol=2e-5,
                               rtol=2e-5)


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
