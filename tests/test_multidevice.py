"""Multi-device numerical equivalence tests.

These spawn a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the flag must be set before jax initializes, and the main test process must
keep seeing 1 device), build a (2 data, 4 model) mesh, and compare the
sharded production paths against unsharded references.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    import dataclasses
    from repro.configs.base import ArchConfig, MoEConfig
    from repro.models import build_model, REFERENCE_PLAN, OFFLOAD_PLAN
    from repro.runtime import sharding as shd
    from repro.launch.mesh import auto_mesh
    from repro.runtime.pspec import axis_rules

    mesh = auto_mesh((2, 4), ("data", "model"))
    rules = shd.make_rules(mesh)

    cfg = ArchConfig(arch_id="mini_moe", family="moe", n_layers=2,
                     d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                     d_ff=96, vocab=256, mlp_act="silu",
                     moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=96,
                                   capacity_factor=8.0),  # no drops
                     tie_embeddings=False)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    batch = m.demo_batch(jax.random.key(1), 8, 32)  # T=256 tokens: %8==0

    plan_off = OFFLOAD_PLAN.replace(attn_kv_chunk=16, wkv_chunk=16,
                                    loss_vocab_chunk=64,
                                    compute_dtype="float32")
    plan_ref = REFERENCE_PLAN.replace(compute_dtype="float32")

    # unsharded reference (no rules context)
    l_ref, _ = jax.jit(lambda p, b: m.loss(p, b, plan_ref))(params, batch)

    # sharded offloaded path (EP MoE + shard_map flash under the mesh)
    p_axes = shd.param_logical_axes(m.param_shapes(), cfg, mesh)
    p_shard = shd.tree_shardings(rules, params, p_axes)
    params_s = jax.device_put(params, p_shard)
    b_shard = shd.tree_shardings(rules, batch, shd.batch_logical_axes(batch))
    batch_s = jax.device_put(batch, b_shard)

    def loss_sharded(p, b):
        with axis_rules(rules):
            return m.loss(p, b, plan_off)

    l_off, _ = jax.jit(loss_sharded, in_shardings=(p_shard, b_shard))(
        params_s, batch_s)
    d = abs(float(l_ref) - float(l_off))
    print(f"ref={float(l_ref):.6f} off={float(l_off):.6f} d={d:.2e}")
    assert d < 5e-3, d

    # a held share (experts 4-7 of 8, unnormalised gates): the EP path
    # computes the same part of the layer as the unsharded one
    cfg_h = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held_first=4, held_count=4, norm_topk=False))
    m_h = build_model(cfg_h)
    params_h = m_h.init(jax.random.key(0))
    lh_ref, _ = jax.jit(lambda p, b: m_h.loss(p, b, plan_ref))(params_h, batch)
    ph_shard = shd.tree_shardings(
        rules, params_h, shd.param_logical_axes(m_h.param_shapes(), cfg_h,
                                                mesh))

    def loss_held(p, b):
        with axis_rules(rules):
            return m_h.loss(p, b, plan_off)

    lh_off, _ = jax.jit(loss_held, in_shardings=(ph_shard, b_shard))(
        jax.device_put(params_h, ph_shard), batch_s)
    dh = abs(float(lh_ref) - float(lh_off))
    print(f"held ref={float(lh_ref):.6f} off={float(lh_off):.6f} d={dh:.2e}")
    assert dh < 5e-3, dh

    # rwkv: shard_map wkv path on the mesh
    from repro.configs import get_config
    cfg2 = get_config("rwkv6_3b").reduced()
    cfg2 = dataclasses.replace(cfg2, d_model=64, rwkv_head_dim=16)  # 4 heads
    m2 = build_model(cfg2)
    params2 = m2.init(jax.random.key(0))
    batch2 = m2.demo_batch(jax.random.key(1), 4, 32)   # B*H = 16: %8==0
    l2_ref, _ = jax.jit(lambda p, b: m2.loss(p, b, plan_ref))(params2, batch2)
    p2_axes = shd.param_logical_axes(m2.param_shapes(), cfg2, mesh)
    p2_shard = shd.tree_shardings(rules, params2, p2_axes)
    params2_s = jax.device_put(params2, p2_shard)
    b2_shard = shd.tree_shardings(rules, batch2, shd.batch_logical_axes(batch2))
    batch2_s = jax.device_put(batch2, b2_shard)

    def loss2(p, b):
        with axis_rules(rules):
            return m2.loss(p, b, plan_off.replace(wkv_chunk=8))

    l2_off, _ = jax.jit(loss2, in_shardings=(p2_shard, b2_shard))(
        params2_s, batch2_s)
    d2 = abs(float(l2_ref) - float(l2_off))
    print(f"rwkv ref={float(l2_ref):.6f} off={float(l2_off):.6f} d={d2:.2e}")
    assert d2 < 5e-3, d2
    print("MULTIDEVICE_OK")
""")


@pytest.mark.slow
def test_sharded_paths_match_reference_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MULTIDEVICE_OK" in res.stdout, (res.stdout[-2000:], res.stderr[-3000:])


_MESH_GA_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import Evaluation, GAConfig, OffloadConfig, Offloader
    from repro.core.frontends.registry import decoded_pattern
    from repro.core.genes import probed_device_count
    from repro.core.objectives import OBJECTIVES
    from repro.service import PlanStore, record_from_result

    assert jax.device_count() == 8
    assert probed_device_count() == 8

    def app(x, w1, w2):
        h = jnp.tanh(x @ w1)
        g = jax.nn.relu(h @ w2)
        y = g * 0.5 + h * 0.1
        return jnp.tanh(y @ w1) + y

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(64, 64)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(64, 64)) * 0.1, jnp.float32)
    args = (x, w1, w2)
    ref = np.asarray(app(*args))

    cfg = OffloadConfig(
        ga=GAConfig(population=10, generations=4, seed=0,
                    objectives=OBJECTIVES),
        options={"example_args": args}, repeats=1)
    off = Offloader(cfg)
    ctx = off.prepare(app)

    # the frontend proposed this host's real meshes alongside the variants
    alpha = ctx.coding.destinations
    mesh_names = [d for d in alpha if d.startswith("mesh:")]
    assert mesh_names == ["mesh:data:2:batch", "mesh:data:4:batch",
                          "mesh:data:8:batch"], alpha
    assert ctx.bundle.mesh_executed

    # deterministic fitness that still GENUINELY executes every chromosome:
    # decode -> substitute (mesh genes become sharded spans on the real
    # 8-device mesh) -> run -> compare against the reference.  Latency is
    # then a deterministic function of what actually ran, so the search and
    # its Pareto front are reproducible.
    engine = ctx.bundle.context["engine"]
    coding = ctx.coding
    mesh_ran = set()

    def fitness(values):
        values = tuple(values)
        impl = decoded_pattern(coding, values, {})
        sub = engine.substitute(
            impl, destinations=coding.destinations_of(values))
        out = jax.jit(sub.fn)(*args)
        ok = bool(np.allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5))
        t = 1.0
        for c in sub.report.choices:
            if c.chosen.startswith("mesh:"):
                mesh_ran.add(c.chosen)
                t -= 0.10                      # genuinely sharded: fastest
            elif c.chosen != "ref":
                t -= 0.04                      # single-device variant
        return Evaluation(values, max(t, 0.05), ok)

    ctx.config.fitness_fn = fitness
    res = off.search(ctx)
    assert mesh_ran, "no chromosome ever reached sharded execution"

    def is_mesh(ev):
        return any(n.startswith("mesh:")
                   for n in coding.destinations_of(ev.bits).values())

    front = res.front
    mesh_points = [ev for ev in front if is_mesh(ev)]
    single_points = [ev for ev in front if not is_mesh(ev)]
    assert mesh_points, [ev.bits for ev in front]
    assert single_points, [ev.bits for ev in front]

    # the winning mesh plan's artifact matches the single-device reference
    best_mesh = min(mesh_points, key=lambda ev: ev.time_s)
    art = off.apply(ctx, best_mesh.bits)
    got = np.asarray(jax.jit(art.fn)(*args))
    assert np.allclose(got, ref, rtol=1e-4, atol=1e-5)
    assert any(c.chosen.startswith("mesh:") and "sharded over" in c.why
               for c in art.report.choices), art.report.choices

    # store -> load -> rehydrate -> serve, with no new search
    rec = record_from_result(res, ctx.fingerprint)
    rec = dataclasses.replace(rec, bits=tuple(best_mesh.bits))
    import tempfile
    store = PlanStore(tempfile.mkdtemp(prefix="mesh_plan_store_"))
    store.put(rec)
    loaded = store.load(ctx.fingerprint)
    assert loaded.mesh_destinations(), loaded.destinations
    art2 = store.rehydrate(loaded, app, config=cfg)
    got2 = np.asarray(jax.jit(art2.fn)(*args))
    assert np.allclose(got2, ref, rtol=1e-4, atol=1e-5)
    print("MESH_GA_OK")
""")


@pytest.mark.slow
def test_mesh_ga_search_on_8_devices_matches_reference():
    """The PR-10 acceptance loop: on a forced-8-device host the GA searches
    placement x parallelism (mesh genes alongside variants), the front
    carries mesh and single-device points, the winning mesh plan's outputs
    match the single-device reference, and the PlanStore round-trips it
    into a servable artifact without a new search."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _MESH_GA_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MESH_GA_OK" in res.stdout, (res.stdout[-2000:],
                                        res.stderr[-3000:])
