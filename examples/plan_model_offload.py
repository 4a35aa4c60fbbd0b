"""The paper's planner at production scale: GA over a model's offload sites
with COMPILED-ARTIFACT fitness on the 256-chip production mesh.

Every chromosome decodes to an ExecPlan, lowers + compiles the train step
(512 placeholder devices), and is scored by the roofline step time; plans
that exceed 16 GB/chip get fitness 0 (the compile-error analogue).  This is
`Offloader.plan` with the module frontend — function-block pass first, GA
over the remaining sites.

Runs a scaled-down architecture so each compile takes ~15 s on this CPU
container; the mechanics are identical for the full configs.

  PYTHONPATH=src python examples/plan_model_offload.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.frontends.registry import OffloadConfig
from repro.core.ga import GAConfig
from repro.core.offload import Offloader
from repro import roofline as rl
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.dryrun import lower_cell


def main():
    print("compile cache:", setup_compile_cache())
    cfg = ArchConfig(arch_id="mini_dense", family="dense", n_layers=3,
                     d_model=512, n_heads=32, n_kv_heads=4, head_dim=16,
                     d_ff=1408, vocab=8000, mlp_act="silu",
                     tie_embeddings=False)
    shape = ShapeSpec("mini_train", 1024, 256, "train")
    mesh = make_production_mesh()
    n_active = cfg.param_count(active_only=True)
    model_flops = rl.model_flops_train(n_active, shape.tokens)

    def lower_fn(plan):
        lowered, _, _ = lower_cell(cfg, shape, mesh, plan)
        return lowered

    ocfg = OffloadConfig(
        frontend="module", ga=GAConfig(population=6, generations=2, seed=0),
        log=print,
        options={"lower_fn": lower_fn, "n_devices": mesh.size,
                 "model_flops": model_flops, "device_kind": "TPU v5 lite"})
    res = Offloader(ocfg).plan(cfg)

    print("\n--- block pass (pattern DB) ---")
    for b in res.block.offloads:
        print(f"  {b.region}: {b.pattern} -> {b.plan_field}")
    print("\n--- GA over remaining sites ---")
    print("  sites:", [s.region for s in res.coding.sites])
    print("  best bits:", res.best.bits)
    base_t = res.baseline.time_s
    best_t = res.best.time_s
    print(f"\nbaseline (ref impls): {base_t*1e3:9.1f} ms/step (roofline est)")
    print(f"planned:              {best_t*1e3:9.1f} ms/step "
          f"-> {base_t/best_t:.2f}x")
    print("final plan:", {
        k: getattr(res.artifact, k)
        for k in ("attn_impl", "norm_impl", "mlp_impl", "qkv_fused",
                  "loss_impl", "remat", "gather_mode")})
    r = res.best.detail.get("roofline", {})
    if r:
        print(f"best-cell terms: compute={r['compute_s']*1e3:.1f}ms "
              f"memory={r['memory_s']*1e3:.1f}ms "
              f"collective={r['collective_s']*1e3:.1f}ms "
              f"dominant={r['dominant']}")


if __name__ == "__main__":
    main()
