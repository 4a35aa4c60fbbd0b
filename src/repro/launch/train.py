"""Training launcher: ``--arch <id>`` selects any assigned architecture.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_0_6b --steps 50 \\
      [--reduced] [--ckpt-dir DIR] [--resume] [--microbatch N]

Runs the supervised training loop (checkpoint/restart + straggler monitor)
on this host's devices.  Full-scale multi-chip configs are exercised via
``repro.launch.dryrun``; this driver actually executes, so it defaults to
the reduced same-family config unless --no-reduced is given.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_config
from repro.core import block_offload_pass, default_db
from repro.core.frontends import module_frontend
from repro.data import Batcher, DataConfig, SyntheticLMDataset
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.models.plan import ExecPlan
from repro.obs import trace as obs_trace
from repro.obs.log import get_logger, setup as setup_logging
from repro.optim import OptimizerConfig
from repro.optim.schedule import make_schedule
from repro.runtime.fault_tolerance import Supervisor
from repro.runtime.train import init_train_state, make_train_step

log = get_logger("launch.train")


def main() -> None:
    setup_logging()
    log.info("compile cache: %s", setup_compile_cache())
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--no-reduced", action="store_true",
                    help="use the FULL config (needs real accelerators)")
    ap.add_argument("--trace", default="",
                    help="write an obs trace journal to this path "
                         "(render with repro.launch.obsreport)")
    args = ap.parse_args()

    with obs_trace.maybe_tracing(args.trace or None):
        _run(args)


def _run(args) -> None:
    cfg = get_config(args.arch)
    if not args.no_reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(model.param_shapes()))
    log.info("arch=%s (%s) params=%.2fM devices=%d", args.arch,
             "full" if args.no_reduced else "reduced", n_params / 1e6,
             len(jax.devices()))

    # the paper's pipeline: pattern-DB block offload decides implementations
    block = block_offload_pass(module_frontend.build_graph(cfg), default_db())
    plan = ExecPlan(compute_dtype="float32", attn_kv_chunk=128,
                    microbatch=args.microbatch).replace(**block.plan_updates)
    log.info("offload plan: %s", block.plan_updates)

    data = SyntheticLMDataset(DataConfig(
        seq_len=args.seq_len, global_batch=args.global_batch,
        vocab=cfg.vocab, seed=0))
    step_fn = jax.jit(make_train_step(
        model, plan, OptimizerConfig(lr=args.lr),
        make_schedule("cosine", peak_lr=args.lr, warmup_steps=10,
                      total_steps=args.steps)), donate_argnums=(0,))

    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    state = init_train_state(model, jax.random.key(0))
    start = 0
    if args.resume and mgr.latest_step() is not None:
        start, state = mgr.restore(state)
        log.info("resumed from step %d", start)

    sup = Supervisor(mgr, ckpt_every=args.ckpt_every,
                     on_straggler=lambda s, dt: log.warning(
                         "straggler step %d: %.0f ms", s, dt * 1e3))
    losses: list = []

    def batch_fn(s):
        return {k: jnp.asarray(v) for k, v in data.batch(s).items()}

    def wrapped(state, batch):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if len(losses) % 10 == 0:
            log.info("step %4d  loss=%.4f", start + len(losses), losses[-1])
        return state, metrics

    state, report = sup.run(state, batch_fn, wrapped, n_steps=args.steps,
                            start_step=start)
    log.info("done: %d steps, %d restarts; loss %.4f -> %.4f",
             report.steps_done, report.restarts, losses[0],
             np.mean(losses[-5:]))


if __name__ == "__main__":
    main()
