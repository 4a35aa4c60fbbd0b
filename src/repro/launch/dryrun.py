import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "10")

"""Multi-pod dry-run: ``lower().compile()`` every (architecture x input
shape) on the production meshes, record memory_analysis / cost_analysis /
roofline terms.

  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama_1_1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--plan tuned]

Results append to experiments/dryrun/<arch>__<shape>__<mesh>.json.  The 512
placeholder devices exist ONLY in this process (XLA_FLAGS is set above,
before any jax import, and nowhere else in the repo).
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro import roofline as rl
from repro.configs.base import (ALL_SHAPES, ARCH_IDS, SHAPES_BY_NAME,
                                ArchConfig, ShapeSpec, get_config)
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.plans import production_plan, tuned_plan
from repro.models.api import Model, build_model
from repro.models.plan import ExecPlan
from repro.obs import trace as obs_trace
from repro.obs.log import get_logger, setup as setup_logging
from repro.optim import OptimizerConfig, adamw_init
from repro.optim.schedule import make_schedule
from repro.runtime import sharding as shd
from repro.runtime.pspec import axis_rules
from repro.runtime.train import TrainState, jit_train_step, make_train_step

Sds = jax.ShapeDtypeStruct

#: the chip the production meshes are made of; its peaks score every cell
#: (the placeholder devices here are CPUs and report no such kind)
TARGET_KIND = "TPU v5 lite"

log = get_logger("launch.dryrun")


# ---------------------------------------------------------------------------
# lowering one cell
# ---------------------------------------------------------------------------


def lower_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, plan: ExecPlan,
               params_dtype=None):
    """Returns (lowered, n_devices, model_flops_global)."""
    model = build_model(cfg)
    rules = shd.make_rules(mesh)
    n_dev = mesh.size
    specs = model.input_specs(shape)
    n_active = cfg.param_count(active_only=True)

    if shape.kind == "train":
        pdtype = params_dtype or jnp.float32
        param_shapes = model.param_shapes(dtype=pdtype)
        p_axes = shd.param_logical_axes(param_shapes, cfg, mesh)
        p_shard = shd.tree_shardings(rules, param_shapes, p_axes)
        opt_shapes = jax.eval_shape(adamw_init, param_shapes)
        opt_axes = type(opt_shapes)(step=(), mu=p_axes, nu=p_axes)
        o_shard = shd.tree_shardings(rules, opt_shapes, opt_axes)
        state_shardings = TrainState(p_shard, o_shard, None)
        state_shapes = TrainState(param_shapes, opt_shapes, None)
        b_axes = shd.batch_logical_axes(specs)
        b_shard = shd.tree_shardings(rules, specs, b_axes)
        step = jit_train_step(
            model, plan, OptimizerConfig(),
            make_schedule(total_steps=10_000), rules,
            state_shardings, b_shard)
        lowered = step.lower(state_shapes, specs)
        mf = rl.model_flops_train(n_active, shape.tokens)
        return lowered, n_dev, mf

    # serving paths use bf16 params
    pdtype = params_dtype or jnp.bfloat16
    param_shapes = model.param_shapes(dtype=pdtype)
    p_axes = shd.param_logical_axes(param_shapes, cfg, mesh)
    p_shard = shd.tree_shardings(rules, param_shapes, p_axes)

    if shape.kind == "prefill":
        b_axes = shd.batch_logical_axes(specs)
        b_shard = shd.tree_shardings(rules, specs, b_axes)

        def prefill(p, inp):
            with axis_rules(rules):
                return model.prefill(p, inp, plan, cache_capacity=shape.seq_len)

        # shard the produced decode state (esp. KV caches) like decode's input
        out_state = jax.eval_shape(prefill, param_shapes, specs)[1]
        st_axes = shd.state_logical_axes(out_state, cfg, mesh)
        st_shard = shd.tree_shardings(rules, out_state, st_axes)
        lowered = jax.jit(prefill, in_shardings=(p_shard, b_shard),
                          out_shardings=(None, st_shard)).lower(
            param_shapes, specs)
        mf = rl.model_flops_infer(n_active, shape.tokens)
        return lowered, n_dev, mf

    # decode: one token against a seq_len cache
    state_specs = specs["state"]
    s_axes = shd.state_logical_axes(state_specs, cfg, mesh)
    s_shard = shd.tree_shardings(rules, state_specs, s_axes)
    tok_shard = shd.tree_shardings(
        rules, specs["token"], shd.batch_logical_axes(specs["token"]))

    def decode(p, tok, st):
        with axis_rules(rules):
            return model.decode(p, tok, st, plan)

    lowered = jax.jit(
        decode, in_shardings=(p_shard, tok_shard, s_shard),
        out_shardings=(None, s_shard),
        donate_argnums=(2,)).lower(param_shapes, specs["token"], state_specs)
    mf = rl.model_flops_infer(n_active, shape.global_batch)
    return lowered, n_dev, mf


# ---------------------------------------------------------------------------
# run + record
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             plan_kind: str = "production", out_dir: str = "experiments/dryrun",
             verbose: bool = True) -> dict:
    setup_logging()          # idempotent — run_cell is also a library entry
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "plan": plan_kind, "status": "skip", "ts": time.time(),
    }
    if not cfg.supports_shape(shape):
        rec["skip_reason"] = cfg.skip_reason(shape)
        _write(rec, out_dir)
        if verbose:
            log.info("[skip] %s x %s: %s", arch, shape_name,
                     rec["skip_reason"])
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        plan = (tuned_plan if plan_kind == "tuned" else production_plan)(cfg, shape)
        t0 = time.time()
        lowered, n_dev, mf = lower_cell(cfg, shape, mesh, plan)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        log.info("%s", mem)   # proves it fits (per-device bytes)
        ca = compiled.cost_analysis()
        log.info("%s", {k: ca[k] for k in ("flops", "bytes accessed")
                        if k in ca})
        roof = rl.analyze(compiled, compiled.as_text(), n_dev,
                          model_flops_global=mf, device_kind=TARGET_KIND)
        live = (getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "generated_code_size_in_bytes", 0))
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
                "live_bytes": live,
                "fits_16gb": bool(live <= 16e9),
            },
            "roofline": roof.summary(),
            "collectives": roof.histogram,
            "xla_cost_analysis": {k: float(ca[k]) for k in
                                  ("flops", "bytes accessed") if k in ca},
        })
        if verbose:
            s = roof.summary()
            log.info("[ok] %s x %s x %s: live=%.2fGB compute=%.2fms "
                     "memory=%.2fms collective=%.2fms dominant=%s "
                     "roofline_frac=%.3f",
                     arch, shape_name, mesh_name, live / 1e9,
                     s["compute_s"] * 1e3, s["memory_s"] * 1e3,
                     s["collective_s"] * 1e3, s["dominant"],
                     s["roofline_fraction"])
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            log.error("[ERROR] %s x %s x %s: %s", arch, shape_name,
                      mesh_name, rec["error"][:300])
    _write(rec, out_dir)
    return rec


def _write(rec: dict, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}__{rec['plan']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    setup_logging()
    log.info("compile cache: %s", setup_compile_cache())
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None,
                    choices=[s.name for s in ALL_SHAPES])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--plan", default="production",
                    choices=["production", "tuned"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already recorded ok/skip")
    ap.add_argument("--trace", default="",
                    help="write an obs trace journal to this path "
                         "(render with repro.launch.obsreport)")
    args = ap.parse_args()

    with obs_trace.maybe_tracing(args.trace or None):
        _run(args)


def _run(args) -> None:
    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = [s.name for s in ALL_SHAPES] if args.all or not args.shape \
        else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    # cheap kinds first so failures surface early; single-pod before multi-pod
    shape_order = {"decode_32k": 0, "prefill_32k": 1, "long_500k": 2, "train_4k": 3}
    cells = [(mp, shape_order.get(sh, 9), arch, sh)
             for mp in meshes for sh in shapes for arch in archs]
    cells.sort()

    n_ok = n_err = n_skip = 0
    for mp, _, arch, shape in cells:
        if args.resume:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            p = os.path.join(args.out,
                             f"{arch}__{shape}__{mesh_name}__{args.plan}.json")
            if os.path.exists(p):
                try:
                    with open(p) as f:
                        old = json.load(f)
                    if old.get("status") in ("ok", "skip"):
                        n_ok += old["status"] == "ok"
                        n_skip += old["status"] == "skip"
                        continue
                except (json.JSONDecodeError, OSError):
                    pass
        rec = run_cell(arch, shape, mp, args.plan, args.out)
        n_ok += rec["status"] == "ok"
        n_err += rec["status"] == "error"
        n_skip += rec["status"] == "skip"
    log.info("done: ok=%d error=%d skip=%d", n_ok, n_err, n_skip)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
