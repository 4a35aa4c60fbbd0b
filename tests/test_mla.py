"""DeepSeek-V2's latent attention (MLA), YaRN rope, the leading dense layer
and the held-expert share of DeepSeekMoE against the benchmark's plain
float32 reference (``chipbench/reference_mla.py``), at a small size on
seeded random weights, on the CPU."""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference_mla  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import OFFLOAD_PLAN, REFERENCE_PLAN, build_model  # noqa: E402
from repro.models import attention as A  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as M  # noqa: E402

with open(os.path.join(ROOT, "chipbench", "configs",
                       "deepseek-v2-lite.json")) as _f:
    CELL = json.load(_f)

#: the cell's configuration at CPU widths: 1 dense + 2 MoE layers, 4 heads,
#: a 32-wide latent, 2 of 8 routed experts held (experts 2-3), top-2
TINY = dict(CELL, hidden_size=64, intermediate_size=128, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            moe_intermediate_size=32, n_routed_experts=2, router_experts=8,
            held_first=2, num_experts_per_tok=2, n_shared_experts=2,
            vocab_size=256, assumed={"capacity_factor": 4.0})

SMALL_OFFLOAD = OFFLOAD_PLAN.replace(attn_kv_chunk=16, loss_vocab_chunk=64)


def _config(c):
    """The registry's DeepSeek-V2-Lite at the sizes of configuration ``c``
    (the reference's keys), holding its share of the routed experts."""
    cfg = get_config("deepseek_v2_lite")
    return dataclasses.replace(
        cfg, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        moe=dataclasses.replace(
            cfg.moe, n_experts=c["router_experts"],
            top_k=c["num_experts_per_tok"],
            d_ff_expert=c["moe_intermediate_size"],
            n_shared_experts=c["n_shared_experts"],
            capacity_factor=c["assumed"]["capacity_factor"],
            held_first=c["held_first"], held_count=c["n_routed_experts"]))


def _model(c):
    cfg = _config(c)
    return cfg, build_model(cfg)


def _weights(m, seed):
    """The program's bf16 initial weights, its norms (offsets from 1) moved
    off zero so that a dropped norm shows."""
    params = m.init(jax.random.key(seed), jnp.bfloat16)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        (x + 0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)
        if "norm" in p[-1].key or p[-1].key in ("ln1", "ln2") else x
        for k, (p, x) in zip(keys, leaves)])


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(0, vocab, (1, n), np.int32)


@pytest.mark.parametrize("plan_name", ["reference", "offload"])
def test_prefill_and_latent_decode_match_the_reference(plan_name):
    """Prefill logits at the prompt's end, then six decode steps through the
    latent cache (teacher-forced), against the reference's full forward.
    The program keeps activations in bf16 (the reference in float32), so
    logits agree to bf16 rounding carried through 3 layers: 3% of the
    logits' spread; a dropped term (rope, the kv norm, a gate, the shared
    expert) moves them by far more."""
    plan = {"reference": REFERENCE_PLAN, "offload": SMALL_OFFLOAD}[plan_name]
    cfg, m = _model(TINY)
    params = _weights(m, 11)
    toks = _tokens(3, 40, TINY["vocab_size"])
    s, steps = 33, 6
    logits, state = jax.jit(lambda p, t: m.prefill(
        p, {"tokens": t}, plan, cache_capacity=48))(params, toks[:, :s])
    got = [logits[:, -1]]
    decode = jax.jit(lambda p, t, st: m.decode(p, t, st, plan))
    for i in range(steps):
        lg, state = decode(params, toks[:, s + i:s + i + 1], state)
        got.append(lg[:, -1])
    got = np.asarray(jnp.stack(got, 1), np.float32)
    want = np.asarray(reference_mla.decoder_logits(
        params, TINY, toks[:, :s + steps], steps + 1))
    spread = want.max() - want.min()
    assert np.abs(got - want).max() < 0.03 * spread, \
        (np.abs(got - want).max(), spread)


def test_absorbed_decode_equals_expanded_form():
    """The absorbed decode (q_nope into the latent, scores and the weighted
    sum in the latent) and the expanded prefill are one set of equations:
    in float32 they agree to rounding at every token of a sequence."""
    cfg, _ = _model(TINY)
    plan = REFERENCE_PLAN.replace(compute_dtype="float32")
    p = A.mla_init(jax.random.key(0), cfg)
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.key(1), (32,))
    x = jax.random.normal(jax.random.key(2), (2, 20, cfg.d_model))
    pos = jnp.arange(20)
    o_full, c, k_pe = A.mla_prefill(x, p, cfg, plan, pos)
    for t in (0, 7, 19):
        valid = jnp.arange(20) < t
        o_t, c_t, pe_t = A.mla_decode(x[:, t:t + 1], p, cfg, plan, c, k_pe,
                                      valid, pos[t:t + 1])
        np.testing.assert_allclose(o_t[:, 0], o_full[:, t], atol=1e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(c_t[:, 0], c[:, t], atol=1e-6)
        np.testing.assert_allclose(pe_t[:, 0], k_pe[:, t], atol=1e-6)


def test_yarn_frequencies_and_softmax_scale():
    """YaRN at DeepSeek-V2-Lite's settings: the ramp runs between
    correction dims 10 and 23 of the 64 rotary dims; below it the
    frequencies are extrapolated (theta^-2i/64), above it interpolated
    (divided by 40); the softmax scale is 192^-1/2 x mscale(40, 0.707)^2."""
    cfg = get_config("deepseek_v2_lite")
    y = cfg.rope_yarn
    assert L.yarn_correction_range(64, 1e4, y) == (10, 23)
    inv = L.yarn_inv_freq(64, 1e4, y)
    extra = 1e4 ** -(np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    ramp = (np.arange(32) - 10) / 13
    mid = slice(11, 23)
    np.testing.assert_allclose(
        inv[mid], extra[mid] / 40 * ramp[mid] + extra[mid] * (1 - ramp[mid]),
        rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4
    assert abs(A.mla_scale(cfg) - 192 ** -0.5 * m * m) < 1e-9
    assert abs(A.mla_scale(cfg) - 0.11472) < 1e-5
    np.testing.assert_allclose(inv, reference_mla.yarn_inv_freq(CELL),
                               rtol=1e-6)
    assert reference_mla.softmax_scale(CELL) == pytest.approx(
        A.mla_scale(cfg), rel=1e-12)


@pytest.mark.parametrize("norm", [False, True])
def test_top_k_gates_left_unnormalised_when_asked(norm):
    cfg = get_config("deepseek_v2_lite")
    e = dataclasses.replace(cfg.moe, norm_topk=norm)
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(0), (5, 64)))
    gates, idx = M._gates(probs, e)
    top = jax.lax.top_k(probs, 6)[0]
    want = top / top.sum(-1, keepdims=True) if norm else top
    np.testing.assert_allclose(gates, want, rtol=1e-6)
    assert idx.shape == (5, 6)


@pytest.mark.parametrize("impl", ["dense_onehot", "scatter_ep"])
def test_held_shares_add_up_to_the_whole_layer(impl):
    """Eight chips each hold 2 of 16 routed experts: the eight shares'
    outputs, the shared experts counted once, add up to the uncut
    reference's MoE layer (every expert, one router)."""
    base = dict(TINY, router_experts=16, num_experts_per_tok=4,
                assumed={"capacity_factor": 4.0})
    h = jax.random.normal(jax.random.key(5), (1, 24, 64), jnp.float32)
    _, whole = _model(dict(base, n_routed_experts=16, held_first=0))
    layer = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                         _weights(whole, 2)["blocks"]["moe"])
    plan = REFERENCE_PLAN.replace(moe_impl=impl, compute_dtype="float32")
    total = 0.0
    for share in range(8):
        c = dict(base, n_routed_experts=2, held_first=2 * share)
        cfg, _ = _model(c)
        p = dict(layer)
        for w in ("w_gate", "w_up", "w_down"):
            p[w] = layer[w][2 * share:2 * share + 2]
        y, _ = M.moe_block(h, p, cfg, plan)
        total = total + y
    shared = M._shared(h.reshape(24, 64), layer, cfg, plan).reshape(h.shape)
    total = total - 7 * shared
    with jax.default_matmul_precision("highest"):
        want = reference_mla._experts(dict(base, n_routed_experts=16,
                                           held_first=0), False,
                                      h[0], layer)
    np.testing.assert_allclose(total[0], want, atol=2e-5, rtol=2e-5)


def test_parameter_counts():
    """The registry's DeepSeek-V2-Lite is the published 15.7B (norms add
    27 x 2 x 2048 + 2048), and the cell's cut holds 902,062,592."""
    cfg = get_config("deepseek-v2-lite")
    assert cfg.param_count() + 27 * 2 * 2048 + 2048 == 15_706_484_224
    cut, model = _model(CELL)
    assert cut.param_count() + 5 * 2 * 2048 + 2048 == 902_062_592
    shapes = model.param_shapes()
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == 902_062_592
    # the reduced config keeps a held share, the dense layer and MLA
    r = get_config("deepseek-v2-lite").reduced()
    assert r.attn_kind == "mla" and r.n_dense_layers == 1
    assert cut.reduced().moe.n_held == 2


def test_decode_state_is_the_latent_cache_only():
    """The cut's decode state holds, per layer and token, the 512-wide
    latent and the 64-wide rotary key: 5 x 576 bf16 values, 5,760 bytes a
    token, in two stacks (the leading dense layer's, the scanned layers');
    the server reports them as ``serve.kv_cache_bytes``."""
    from repro.obs import metrics as obs_metrics
    from repro.runtime.serve import ServeConfig, Server

    cfg, m = _model(CELL)
    from repro.configs.base import ShapeSpec

    cap = 65_536 + 32
    state = m.state_specs(ShapeSpec("long_doc", cap, 1, "decode"))
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    names = {tuple(k.key for k in p) for p, _ in leaves}
    assert names == {("cache_len",), ("pre_kv", "c"), ("pre_kv", "k_pe"),
                     ("kv", "c"), ("kv", "k_pe")}
    cache = sum(x.size * x.dtype.itemsize for p, x in leaves
                if p[0].key != "cache_len")
    assert cache == 5 * 576 * 2 * cap

    tcfg, tm = _model(TINY)
    params = _weights(tm, 1)
    Server(tm, params, REFERENCE_PLAN, ServeConfig(max_new_tokens=3)) \
        .generate({"tokens": _tokens(1, 9, 256)})
    want = 3 * (32 + 8) * 2 * (9 + 3) + 4
    assert obs_metrics.gauge("serve.kv_cache_bytes").value == want
