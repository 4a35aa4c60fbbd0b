"""Optimizer, schedules, gradient compression, data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.data import Batcher, DataConfig, SyntheticLMDataset
from repro.optim import (OptimizerConfig, adamw_init, adamw_update,
                         compress_int8, decompress_int8, ef_compress_update,
                         ef_init, make_schedule)


def test_adamw_minimizes_quadratic():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros((3,))}
    state = adamw_init(params)
    cfg = OptimizerConfig(lr=0.1, weight_decay=0.0)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
        params, state, _ = adamw_update(g, state, params, cfg, cfg.lr)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_grad_clipping_bounds_update():
    params = {"w": jnp.zeros((4,))}
    state = adamw_init(params)
    cfg = OptimizerConfig(lr=1.0, clip_norm=1.0, weight_decay=0.0)
    huge = {"w": jnp.full((4,), 1e6)}
    p2, _, metrics = adamw_update(huge, state, params, cfg, cfg.lr)
    assert float(metrics["grad_norm"]) > 1e5
    assert np.all(np.abs(np.asarray(p2["w"])) < 10.0)


def test_schedule_shapes():
    s = make_schedule("cosine", peak_lr=1e-3, warmup_steps=10, total_steps=100)
    assert float(s(0)) == 0.0
    assert float(s(10)) == pytest.approx(1e-3)
    assert float(s(100)) == pytest.approx(1e-4, rel=0.05)
    assert float(s(5)) == pytest.approx(5e-4)


@given(st.integers(0, 2 ** 16), st.floats(0.1, 100.0))
@settings(max_examples=25, deadline=None)
def test_property_int8_roundtrip_error_bound(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)) * scale, jnp.float32)
    q, s = compress_int8(x)
    err = np.abs(np.asarray(decompress_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6  # half-ulp of the quant grid


def test_error_feedback_tracks_exact_sgd():
    """EF-int8 compressed gradient sum over steps matches exact within the
    final quantization residual (the EF guarantee)."""
    rng = np.random.default_rng(3)
    params = {"w": jnp.zeros((32,))}
    comp = ef_init(params)
    exact_sum = np.zeros(32)
    applied_sum = np.zeros(32)
    for i in range(50):
        g = {"w": jnp.asarray(rng.normal(size=32), jnp.float32)}
        exact_sum += np.asarray(g["w"])
        qs, scales, comp = ef_compress_update(g, comp)
        applied_sum += np.asarray(decompress_int8(qs["w"], scales["w"]))
    resid = np.abs(np.asarray(comp.error["w"]))
    np.testing.assert_allclose(applied_sum, exact_sum, atol=resid.max() + 1e-5)
    # and the residual stays bounded (no divergence)
    assert resid.max() < 0.2


def test_synthetic_data_deterministic_and_shard_aware():
    cfg = DataConfig(seq_len=32, global_batch=8, vocab=100, seed=7)
    ds = SyntheticLMDataset(cfg)
    b1 = ds.batch(5, host_id=0, n_hosts=2)
    b2 = ds.batch(5, host_id=0, n_hosts=2)
    b3 = ds.batch(5, host_id=1, n_hosts=2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])  # deterministic
    assert not np.array_equal(b1["tokens"], b3["tokens"])      # per-host shard
    assert b1["tokens"].shape == (4, 32)                       # 8 / 2 hosts
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])


def test_batcher_resumes_from_step():
    cfg = DataConfig(seq_len=16, global_batch=4, vocab=50, seed=1)
    ds = SyntheticLMDataset(cfg)
    b = Batcher(ds, start_step=10)
    step, batch = next(b)
    b.close()
    assert step == 10
    np.testing.assert_array_equal(batch["tokens"], ds.batch(10)["tokens"])
