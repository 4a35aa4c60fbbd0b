"""End-to-end system behaviour: supervised training of a real (reduced)
model with checkpoint/restart, the serving loop, and the compressed-DP
step's convergence parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLMDataset
from repro.launch.mesh import auto_mesh
from repro.models import REFERENCE_PLAN, build_model
from repro.models.plan import ExecPlan
from repro.optim import OptimizerConfig
from repro.optim.schedule import make_schedule
from repro.runtime.fault_tolerance import Supervisor
from repro.runtime.serve import ServeConfig, Server
from repro.runtime.train import (init_train_state, make_compressed_dp_step,
                                 make_train_step)

PLAN = ExecPlan(compute_dtype="float32")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3_0_6b").reduced()
    model = build_model(cfg)
    data = SyntheticLMDataset(DataConfig(seq_len=32, global_batch=4,
                                         vocab=cfg.vocab, seed=0))
    return cfg, model, data


def test_train_loss_decreases(setup):
    cfg, model, data = setup
    state = init_train_state(model, jax.random.key(0))
    opt = OptimizerConfig(lr=3e-3, weight_decay=0.0)
    step = jax.jit(make_train_step(model, PLAN, opt,
                                   make_schedule("constant", peak_lr=3e-3,
                                                 warmup_steps=1)))
    losses = []
    for i in range(20):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1


def test_supervised_training_with_failures(setup, tmp_path):
    cfg, model, data = setup
    state = init_train_state(model, jax.random.key(0))
    opt = OptimizerConfig(lr=1e-3)
    step = jax.jit(make_train_step(model, PLAN, opt,
                                   make_schedule("constant", peak_lr=1e-3,
                                                 warmup_steps=1)))

    def batch_fn(i):
        return {k: jnp.asarray(v) for k, v in data.batch(i).items()}

    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    sup = Supervisor(mgr, ckpt_every=4, max_restarts=3)
    hit = set()

    def injector(s):
        if s == 6 and s not in hit:
            hit.add(s)
            return True
        return False

    state, report = sup.run(state, batch_fn, step, n_steps=12,
                            failure_injector=injector)
    assert report.restarts == 1
    assert len(report.losses) >= 12
    assert int(state.opt.step) == 12


def test_compressed_dp_step_tracks_exact(setup):
    """int8-EF compressed gradients converge like exact (single-axis mesh)."""
    cfg, model, data = setup
    mesh = auto_mesh((1,), ("data",))

    def run(compress):
        state = init_train_state(model, jax.random.key(1),
                                 with_compression=True)
        opt = OptimizerConfig(lr=3e-3, weight_decay=0.0)
        step = make_compressed_dp_step(
            model, PLAN, opt, make_schedule("constant", peak_lr=3e-3,
                                            warmup_steps=1),
            mesh, compress=compress)
        losses = []
        for i in range(10):
            batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        return losses

    exact = run(False)
    comp = run(True)
    assert all(np.isfinite(comp))
    # same data, same init: trajectories should stay close at 1 pod
    np.testing.assert_allclose(comp, exact, rtol=0.05, atol=0.05)


def test_serving_loop_greedy_decode(setup):
    cfg, model, data = setup
    params = model.init(jax.random.key(0))
    server = Server(model, params, REFERENCE_PLAN,
                    ServeConfig(max_new_tokens=6))
    toks = jnp.asarray(data.batch(0)["tokens"][:2, :16])
    out = server.generate({"tokens": toks})
    assert out.shape == (2, 6)
    assert np.all(out >= 0) and np.all(out < cfg.vocab)
    # greedy decode is deterministic
    out2 = server.generate({"tokens": toks})
    np.testing.assert_array_equal(out, out2)
