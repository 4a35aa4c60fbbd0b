"""Compile every program a cell runs for a described TPU v5e, without a
chip, and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python chipbench/aot.py [cell ...]

Serving cells: the prefill of each prompt bucket and the decode step, under
the module frontend's base plan and under the all-offload plan (the GA picks
between such plans in set-up).  Planning cells: the planned program as
written.  Exits non-zero when a program does not compile or does not fit.
"""
from __future__ import annotations

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness, weights
    from chipbench.drivers import plan as plan_driver
    from chipbench.drivers import serve
    from chipbench.flops import head_dim
    from chipbench.peaks import peaks_for

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    hbm = peaks_for(topo.devices[0].device_kind).hbm_bytes
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=dev)
    bench = harness.load_benchmark()
    cells = argv or [w["name"] for w in bench["workloads"]]
    bad = 0

    def report(what: str, compiled) -> None:
        nonlocal bad
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes
                + m.generated_code_size_in_bytes)
        fits = need < hbm
        bad += not fits
        print(f"{what}: arguments {m.argument_size_in_bytes} output "
              f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} alias "
              f"{m.alias_size_in_bytes} -> {need / 1e9:.3f} GB of "
              f"{hbm / 1e9:.0f} GB {'fits' if fits else 'DOES NOT FIT'}",
              flush=True)

    for cell in cells:
        w, c, t, _ = harness.load_cell(bench, cell)
        if t["driver"] == "plan":
            fn, _ = plan_driver.program(t)
            shapes = dict(batch=t["batch"], seq=t["seq"],
                          n_heads=c["num_attention_heads"],
                          n_kv_heads=c["num_key_value_heads"],
                          head_dim=head_dim(c), d_model=c["hidden_size"])
            _, make = plan_driver.program(t)
            args = [spec(a.shape, a.dtype) for a in jax.eval_shape(
                functools.partial(make, **shapes), jax.random.key(0))]
            report(f"{cell} {t['program']}",
                   jax.jit(fn).lower(*args).compile())
            continue
        from repro.models import OFFLOAD_PLAN, build_model
        from repro.models.plan import ExecPlan

        model = build_model(serve.arch_config(c))
        params = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                              jax.eval_shape(lambda: weights.make(c, 0)))
        for plan_name, plan in (("base", ExecPlan()),
                                ("offload", OFFLOAD_PLAN)):
            for s in t["prompt_lengths"]:
                cap = s + t["new_tokens"]
                tokens = {"tokens": spec((t["batch"], s), jnp.int32)}
                prefill = jax.jit(lambda p, inp, cap=cap, plan=plan:
                                  model.prefill(p, inp, plan,
                                                cache_capacity=cap))
                report(f"{cell} {plan_name} prefill {t['batch']}x{s}",
                       prefill.lower(params, tokens).compile())
                state = jax.tree.map(
                    lambda x: spec(x.shape, x.dtype),
                    jax.eval_shape(prefill, params, tokens)[1])
                decode = jax.jit(lambda p, tok, st, plan=plan:
                                 model.decode(p, tok, st, plan),
                                 donate_argnums=(2,))
                report(f"{cell} {plan_name} decode {t['batch']}x1 cap {cap}",
                       decode.lower(params, spec((t["batch"], 1), jnp.int32),
                                    state).compile())
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
