"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, output shapes + no NaNs; reference vs offloaded plan equivalence
(the PCAST check); prefill+decode vs full-sequence consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import OFFLOAD_PLAN, REFERENCE_PLAN, build_model

SMALL_OFFLOAD = OFFLOAD_PLAN.replace(
    attn_q_chunk=16, attn_kv_chunk=16, rglru_chunk=16, wkv_chunk=16,
    loss_vocab_chunk=64)


@pytest.fixture(scope="module")
def built():
    out = {}
    for arch in ARCH_IDS:
        cfg = get_config(arch).reduced()
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        batch = m.demo_batch(jax.random.key(1), 2, 64)
        out[arch] = (cfg, m, params, batch)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_configs_exact(arch):
    """The registry carries the exact assigned architecture numbers."""
    cfg = get_config(arch)
    assert cfg.n_layers > 0 and cfg.d_model > 0 and cfg.vocab > 0
    n = cfg.param_count()
    n_active = cfg.param_count(active_only=True)
    assert n >= n_active > 0
    if cfg.moe is not None:
        assert n > n_active  # MoE: total params exceed active


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(built, arch):
    cfg, m, params, batch = built[arch]
    loss, metrics = jax.jit(lambda p, b: m.loss(p, b, REFERENCE_PLAN))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss))
    assert 3.0 < float(loss) < 9.0  # ~ln(vocab) at random init
    # one grad step produces finite grads of matching structure
    g = jax.grad(lambda p: m.loss(p, batch, REFERENCE_PLAN)[0])(params)
    leaves = jax.tree_util.tree_leaves(g)
    assert all(np.all(np.isfinite(np.asarray(l, np.float32))) for l in leaves)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_offload_plan_matches_reference(built, arch):
    """PCAST analogue: offloaded implementations must agree with reference."""
    cfg, m, params, batch = built[arch]
    l_ref, _ = jax.jit(lambda p, b: m.loss(p, b, REFERENCE_PLAN))(params, batch)
    l_off, _ = jax.jit(lambda p, b: m.loss(p, b, SMALL_OFFLOAD))(params, batch)
    # MoE capacity dropping causes small diffs; dense paths are tighter
    tol = 5e-3 if cfg.moe is not None else 5e-4
    assert abs(float(l_ref) - float(l_off)) < tol


@pytest.mark.parametrize("arch", ["tinyllama_1_1b", "qwen3_0_6b", "olmoe_1b_7b",
                                  "recurrentgemma_2b", "rwkv6_3b",
                                  "whisper_small", "llava_next_mistral_7b",
                                  "deepseek_v2_lite"])
def test_decode_matches_full_forward(built, arch):
    cfg, m, params, _ = built[arch]
    S = 64 if cfg.family == "hybrid" else 33
    batch = m.demo_batch(jax.random.key(2), 2, S + 1 + (cfg.vision_patches or 0))
    toks = batch["tokens"]
    inputs = {k: v for k, v in batch.items() if k != "labels"}
    inp_s = dict(inputs)
    inp_s["tokens"] = toks[:, :-1]
    cap = toks.shape[1] + (cfg.vision_patches or 0) + 4
    _, state = m.prefill(params, inp_s, REFERENCE_PLAN, cache_capacity=cap)
    lg_step, state2 = m.decode(params, toks[:, -1:], state, REFERENCE_PLAN)
    lg_full, _ = m.prefill(params, inputs, REFERENCE_PLAN)
    d = float(jnp.max(jnp.abs(lg_step.astype(jnp.float32)
                              - lg_full.astype(jnp.float32))))
    assert d < 2e-2
    assert int(state2["cache_len"]) == int(state["cache_len"]) + 1


@pytest.mark.parametrize("arch", ["qwen3_0_6b", "olmoe_1b_7b",
                                  "recurrentgemma_2b", "whisper_small",
                                  "llava_next_mistral_7b"])
def test_multistep_decode_matches_full_forward(built, arch):
    """Six decode steps after a prefill: each step's logits match the full
    forward at its position, and every cache slot that no step wrote keeps
    what the prefill left there (the cross-attention cache, all of it).  The
    hybrid's prompt ends three slots before its ring's end, so the steps
    wrap the ring."""
    cfg, m, params, _ = built[arch]
    steps = 6
    s = 2 * cfg.local_window - 3 if cfg.family == "hybrid" else 24
    batch = m.demo_batch(jax.random.key(3), 2,
                         s + steps + (cfg.vision_patches or 0))
    toks = batch["tokens"]
    inputs = {k: v for k, v in batch.items() if k != "labels"}
    cap = toks.shape[1] + (cfg.vision_patches or 0) + 4
    _, first = m.prefill(params, {**inputs, "tokens": toks[:, :s]},
                         REFERENCE_PLAN, cache_capacity=cap)
    decode = jax.jit(lambda p, t, st: m.decode(p, t, st, REFERENCE_PLAN))
    state, written = first, []
    for i in range(steps):
        written.append(int(state["cache_len"]))
        lg_step, state = decode(params, toks[:, s + i:s + i + 1], state)
        lg_full, _ = m.prefill(params, {**inputs, "tokens": toks[:, :s + i + 1]},
                               REFERENCE_PLAN)
        d = float(jnp.max(jnp.abs(lg_step.astype(jnp.float32)
                                  - lg_full.astype(jnp.float32))))
        assert d < 2e-2, (i, d)
    assert int(state["cache_len"]) == written[0] + steps

    ring = cfg.attn_kind == "local"
    after = dict(jax.tree_util.tree_flatten_with_path(state)[0])
    caches = 0
    for path, before in jax.tree_util.tree_flatten_with_path(first)[0]:
        name = path[-1].key
        if name not in ("k", "v", "xk", "xv"):
            continue
        caches += 1
        sc = before.shape[3]                      # (L, B, Hkv, Sc, D)
        hit = set() if name in ("xk", "xv") else \
            {p % sc if ring else p for p in written}
        if not ring:
            assert all(p >= written[0] for p in hit)
        keep = np.array([j for j in range(sc) if j not in hit])
        np.testing.assert_array_equal(np.asarray(after[path])[:, :, :, keep],
                                      np.asarray(before)[:, :, :, keep])
    assert caches == (4 if cfg.family == "encdec" else 2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_input_specs_cover_model(built, arch):
    """input_specs must be sufficient to trace every step kind (this is what
    the dry-run lowers)."""
    from repro.configs.base import ShapeSpec
    cfg, m, params, _ = built[arch]
    train = ShapeSpec("t", 64, 2, "train")
    specs = m.input_specs(train)
    jax.eval_shape(lambda p, b: m.loss(p, b, REFERENCE_PLAN), params, specs)
    dec = ShapeSpec("d", 64, 2, "decode")
    specs_d = m.input_specs(dec)
    jax.eval_shape(lambda p, t, s: m.decode(p, t, s, REFERENCE_PLAN),
                   params, specs_d["token"], specs_d["state"])


REGIONS = {"embed", "attention", "kv_cache", "mlp", "moe", "norm", "head"}


@pytest.mark.parametrize("plan_name", ["reference", "offload"])
@pytest.mark.parametrize("arch", ["qwen3_0_6b", "olmoe_1b_7b",
                                  "deepseek_v2_lite"])
def test_compiled_serve_steps_name_every_matmul_region(arch, plan_name):
    """The served prefill and decode compile to modules named for their
    step, and every dot or convolution in them carries a model region in
    its ``op_name`` metadata (what the profiler trace reports as the op's
    scope)."""
    import re

    from repro.runtime.serve import ServeConfig, Server

    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    plan = REFERENCE_PLAN if plan_name == "reference" else SMALL_OFFLOAD
    params = m.init(jax.random.key(0))
    hlo = Server(m, params, plan, ServeConfig(max_new_tokens=4)).step_hlo(
        2, 16)
    assert set(hlo) == {"jit_serve_prefill", "jit_serve_decode"}
    for module, text in hlo.items():
        assert text.startswith(f"HloModule {module}")
        matmuls = [ln for ln in text.splitlines()
                   if re.search(r"= \S+ (dot|convolution)\(", ln)]
        assert matmuls
        for ln in matmuls:
            op_name = re.search(r'op_name="([^"]*)"', ln)
            assert op_name and REGIONS & set(op_name.group(1).split("/")), \
                (module, ln[:160])
