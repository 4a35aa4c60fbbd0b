"""Train-step builders.

* ``make_train_step``   — the production jit step: loss -> grads -> AdamW,
  sharding via the logical-axis rules (DP/FSDP/TP/EP from one table), buffer
  donation for params/optimizer state.
* ``make_compressed_dp_step`` — shard_map data-parallel variant with
  hierarchical gradient reduction: fp32 reduce inside a pod, error-feedback
  int8 across pods (the slow hop).  Used by the compression benchmark and
  example; the mechanism is exact-tracking thanks to error feedback.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.api import Model
from repro.models.plan import ExecPlan
from repro.optim import (AdamWState, CompressionState, OptimizerConfig,
                         adamw_init, adamw_update, ef_compress_update, ef_init)
from repro.runtime.pspec import ShardingRules, axis_rules


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    comp: Optional[CompressionState]


def init_train_state(model: Model, rng: jax.Array, with_compression: bool = False,
                     dtype=jnp.float32) -> TrainState:
    params = model.init(rng, dtype=dtype)
    return TrainState(params, adamw_init(params),
                      ef_init(params) if with_compression else None)


def make_train_step(model: Model, plan: ExecPlan, opt_cfg: OptimizerConfig,
                    schedule: Callable, rules: Optional[ShardingRules] = None):
    """Returns train_step(state, batch) -> (state, metrics).  Pure; jit/lower
    it under ``with axis_rules(rules)`` so activation constraints resolve."""

    def grads_of(params, batch):
        def loss_fn(p):
            return model.loss(p, batch, plan)
        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(state: TrainState, batch: dict):
        mb = max(plan.microbatch, 1)
        if mb == 1:
            (loss, metrics), grads = grads_of(state.params, batch)
        else:
            # gradient accumulation: scan over microbatches; activation
            # memory scales by 1/mb at the cost of mb weight re-reads
            def split(x):
                b = x.shape[0]
                assert b % mb == 0, (b, mb)
                return x.reshape(mb, b // mb, *x.shape[1:])
            micro = jax.tree_util.tree_map(split, batch)

            def acc_fn(carry, mbatch):
                g_acc, m_acc = carry
                (_, metrics), grads = grads_of(state.params, mbatch)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32) / mb, g_acc, grads)
                m_acc = jax.tree_util.tree_map(
                    lambda a, m: a + m / mb, m_acc, metrics)
                return (g_acc, m_acc), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            m0 = jax.eval_shape(lambda: grads_of(state.params, jax.tree_util.tree_map(
                lambda x: x[0], micro))[0][1])
            m0 = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), m0)
            (grads, metrics), _ = jax.lax.scan(acc_fn, (g0, m0), micro)
        lr = schedule(state.opt.step)
        new_p, new_opt, om = adamw_update(grads, state.opt, state.params,
                                          opt_cfg, lr)
        metrics = dict(metrics)
        metrics.update(om)
        return TrainState(new_p, new_opt, state.comp), metrics

    return train_step


def jit_train_step(model: Model, plan: ExecPlan, opt_cfg: OptimizerConfig,
                   schedule: Callable, rules: ShardingRules,
                   state_shardings, batch_shardings, donate: bool = True):
    """AOT-friendly jitted step with shardings + donation."""
    step = make_train_step(model, plan, opt_cfg, schedule, rules)

    def traced(state, batch):
        with axis_rules(rules):
            return step(state, batch)

    return jax.jit(
        traced,
        in_shardings=(state_shardings, batch_shardings),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate else (),
    )


# ---------------------------------------------------------------------------
# compressed hierarchical-DP step (shard_map over (pod, data))
# ---------------------------------------------------------------------------


def make_compressed_dp_step(model: Model, plan: ExecPlan,
                            opt_cfg: OptimizerConfig, schedule: Callable,
                            mesh, compress: bool = True):
    """Pure data-parallel step over mesh axes (pod?, data) with hierarchical
    gradient reduction: exact fp32 psum within a pod, EF-int8 across pods.

    Params are replicated; batch is sharded over all DP axes.  Suitable for
    models that fit one device (the compression mechanism demo); at scale the
    same pattern rides on the FSDP step's pod axis.
    """
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    has_pod = "pod" in mesh.shape
    n_pods = mesh.shape.get("pod", 1)

    batch_spec = P(dp_axes)
    rep = P()

    def local_step(state: TrainState, batch: dict):
        def loss_fn(p):
            return model.loss(p, batch, plan)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        # exact reduction inside the pod (fast ICI)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g.astype(jnp.float32), "data"), grads)
        comp = state.comp
        if has_pod:
            if compress and comp is not None:
                qs, scales, comp = ef_compress_update(grads, comp)
                # int8 payload on the slow hop; int16 accumulator is exact
                # for <= 256 pods (127 * 256 < 2^15)
                summed = jax.tree_util.tree_map(
                    lambda q: jax.lax.psum(q.astype(jnp.int16), "pod"), qs)
                grads = jax.tree_util.tree_map(
                    lambda s, sc: s.astype(jnp.float32) * sc / n_pods,
                    summed, scales)
            else:
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, "pod"), grads)
        lr = schedule(state.opt.step)
        new_p, new_opt, om = adamw_update(grads, state.opt, state.params,
                                          opt_cfg, lr)
        metrics = dict(metrics)
        metrics.update(om)
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, dp_axes[0]) if dp_axes else m, metrics)
        return TrainState(new_p, new_opt, comp), metrics

    state_specs = TrainState(rep, AdamWState(rep, rep, rep), rep)

    smapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(state_specs, batch_spec),
        out_specs=(state_specs, rep),
        check_vma=False)
    return jax.jit(smapped, donate_argnums=(0,))
