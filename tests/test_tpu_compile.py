"""Compile-only checks for a described TPU v5e: the Pallas kernels and the
full-width qwen3_0_6b serving programs must pass the chip's compiler.

Nothing runs here and nothing is timed.  The topology is described inside
a module fixture (never while the file is imported), and the persistent
compilation cache is off for these compiles: entries written for a
described chip cannot be read back without one.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import rglru_scan as rg
from repro.kernels import rmsnorm as rn
from repro.kernels import wkv6 as wk
from repro.models import OFFLOAD_PLAN, REFERENCE_PLAN, build_model
from repro.roofline import peaks_for

V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    assert topo.devices[0].device_kind == V5E
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


#: kernel -> (callable, argument shapes/dtypes) at the widths the smoke run
#: and the model configs use
_KERNELS = {
    # qwen3 attention: 16 q heads over 8 kv heads, head_dim 128, seq 2048
    "flash_attention": (
        functools.partial(fa.flash_attention_bh, causal=True,
                          scale=128 ** -0.5, blk_q=128, blk_k=128, group=2,
                          interpret=False),
        [((16, 2048, 128),), ((8, 2048, 128),), ((8, 2048, 128),)]),
    # recurrentgemma_2b: d_rnn 2560
    "rglru_scan": (
        functools.partial(rg.rglru_scan, chunk=256, d_block=128,
                          interpret=False),
        [((2, 2048, 2560), jnp.float32)] * 2),
    # qwen3 residual stream: 4 x 1024 rows of d_model 1024
    "rmsnorm": (
        functools.partial(rn.rmsnorm, blk_rows=256, interpret=False),
        [((4096, 1024),), ((1024,),)]),
    # rwkv6_3b: 40 heads of 64 over seq 2048
    "wkv6": (
        functools.partial(wk.wkv6, chunk=64, interpret=False),
        [((40, 2048, 64),)] * 4 + [((40, 1, 64),)]),
}


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _KERNELS[name]
    args = [_spec(one_chip, *s) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _qwen3_serving_lowered(one_chip, plan, batch, prompt, new):
    cfg = get_config("qwen3_0_6b")
    model = build_model(cfg)
    on = functools.partial(jax.tree_util.tree_map,
                           lambda s: _spec(one_chip, s.shape, s.dtype))
    params = on(model.param_shapes(jnp.bfloat16))
    tokens = {"tokens": _spec(one_chip, (batch, prompt), jnp.int32)}
    cap = prompt + new
    prefill = jax.jit(lambda p, inp: model.prefill(p, inp, plan,
                                                   cache_capacity=cap))
    state = on(jax.eval_shape(prefill, params, tokens)[1])
    decode = jax.jit(lambda p, tok, st: model.decode(p, tok, st, plan),
                     donate_argnums=(2,))
    token = _spec(one_chip, (batch, 1), jnp.int32)
    return (prefill.lower(params, tokens), decode.lower(params, token, state))


def _qwen3_serving_programs(one_chip, plan, batch=4, prompt=128, new=32):
    return tuple(low.compile() for low in
                 _qwen3_serving_lowered(one_chip, plan, batch, prompt, new))


@pytest.mark.parametrize("plan_name", ["reference", "offload"])
def test_qwen3_serving_fits_one_v5e(one_chip, plan_name):
    plan = {"reference": REFERENCE_PLAN, "offload": OFFLOAD_PLAN}[plan_name]
    hbm = peaks_for(V5E).hbm_bytes
    for compiled in _qwen3_serving_programs(one_chip, plan):
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                + mem.generated_code_size_in_bytes)
        # bf16 weights alone are ~1.2 GB; the program must fit the chip
        assert 1e9 < live < hbm, live


def _loop_bodies(hlo: str) -> dict:
    """Instruction lines of each while loop's body computation, by name."""
    names = {b.lstrip("%") for b in re.findall(r"body=%?([\w.\-]+)", hlo)}
    out = {}
    for comp in re.split(r"\n(?=\S)", hlo):
        head = comp.split(" ", 1)[0].lstrip("%")
        if head in names:
            out[head] = comp.splitlines()[1:]
    return out


def test_qwen3_decode_reads_each_cache_once(one_chip):
    """Full-width qwen3_0_6b decode at batch 32 and cache capacity 640 (the
    long bucket of the decode cell).  Attention reads each layer's cache in
    place and the token is written once after the layer loop, so nothing in
    the loop's body makes a cache-layer-sized or stacked-cache-sized array
    (a carried cache took a slice, a relayout and a write-back of the whole
    stack per layer).  XLA's bytes accessed, the body counted once, read
    0.99 GB with the carried cache and must stay under 0.6 GB; scratch
    memory must not exceed the 42,297,856 bytes it needed then."""
    _, low = _qwen3_serving_lowered(one_chip, REFERENCE_PLAN, batch=32,
                                    prompt=512, new=128)
    compiled = low.compile()
    cfg = get_config("qwen3_0_6b")
    layer = sorted((32, cfg.n_kv_heads, 640, cfg.resolved_head_dim))
    shapes = (layer, sorted(layer + [cfg.n_layers]))
    aliases = {"parameter", "get-tuple-element", "tuple", "bitcast"}
    bodies = _loop_bodies(compiled.as_text())
    assert bodies
    made = []
    for name, lines in bodies.items():
        for ln in lines:
            m = re.match(r"\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                         ln)
            if not m or m.group(3) in aliases:
                continue
            dims = sorted(int(d) for d in m.group(2).split(",") if d not in ("", "1"))
            if dims in shapes:
                made.append((name, m.group(1), m.group(3)))
    assert not made, made
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 0.6e9, cost["bytes accessed"]
    assert compiled.memory_analysis().temp_size_in_bytes <= 42_297_856


# ---------------------------------------------------------------------------
# DeepSeek-V2-Lite's cut (the deepseek-v2-lite.long-doc cell): latent
# attention at 65,536 tokens on one chip
# ---------------------------------------------------------------------------


def _mla_cut():
    """The cell's cut: the dense layer and four MoE layers, routed experts
    0-7 of 64 held, dropless (capacity factor 64/6)."""
    import dataclasses

    cfg = get_config("deepseek_v2_lite")
    return dataclasses.replace(cfg, n_layers=5, moe=dataclasses.replace(
        cfg.moe, held_count=8, capacity_factor=64 / 6))


def _lower_prefill(one_chip, cfg, plan, batch, prompt, cap):
    model = build_model(cfg)
    on = functools.partial(jax.tree_util.tree_map,
                           lambda s: _spec(one_chip, s.shape, s.dtype))
    params = on(model.param_shapes(jnp.bfloat16))
    tokens = {"tokens": _spec(one_chip, (batch, prompt), jnp.int32)}
    # argument names are part of the HLO (parameter names): keep them
    prefill = jax.jit(lambda p, i: model.prefill(p, i, plan,
                                                 cache_capacity=cap))
    return model, on, params, prefill.lower(params, tokens), \
        on(jax.eval_shape(prefill, params, tokens)[1])


def test_module_frontend_refuses_a_plan_that_does_not_fit(one_chip):
    """The module frontend's fitness compiles each candidate for the chip:
    naive attention at 65,536 tokens needs a 275 GB score tensor and is
    scored invalid on its memory; the chunked plan fits, and is valid."""
    from repro.core.fitness import CostModelFitness
    from repro.models.plan import ExecPlan

    cfg = _mla_cut()
    plans = [ExecPlan(), ExecPlan(attn_impl="chunked")]
    fit = CostModelFitness(
        lower=lambda bits: _lower_prefill(one_chip, cfg, plans[bits[0]], 1,
                                          65_536, 65_568)[3],
        n_devices=1, device_kind=V5E)
    naive, chunked = fit((0,)), fit((1,))
    assert not naive.valid and naive.time_s == float("inf")
    assert "RESOURCE_EXHAUSTED" in naive.detail["error"] \
        or "OOM" in naive.detail["error"], naive.detail
    assert chunked.valid, chunked.detail
    # weights 1.8 GB, the 378 MB latent cache out, scratch: under 16 GB
    assert 2e9 < chunked.detail["live_bytes"] < 8e9, chunked.detail


def test_mla_decode_state_is_the_latent_cache_only(one_chip):
    """The cut's decode step at capacity 65,568 takes and returns only the
    latent stacks (L, B, S, 512) and (L, B, S, 64), written in place, and
    no instruction makes anything shaped like a per-head cache."""
    cfg = _mla_cut()
    model, on, params, _, state = _lower_prefill(
        one_chip, cfg, REFERENCE_PLAN, 1, 65_536, 65_568)
    shapes = {tuple(x.shape) for x in jax.tree_util.tree_leaves(state)}
    assert shapes == {(), (1, 1, 65_568, 512), (1, 1, 65_568, 64),
                      (4, 1, 65_568, 512), (4, 1, 65_568, 64)}
    decode = jax.jit(lambda p, t, st: model.decode(p, t, st, REFERENCE_PLAN),
                     donate_argnums=(2,))
    compiled = decode.lower(params, _spec(one_chip, (1, 1), jnp.int32),
                            state).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * 576 * 2 * 65_568
    assert mem.temp_size_in_bytes < 64e6
    per_head = {m for m in re.findall(r"\w+\[([\d,]+)\]",
                                      compiled.as_text())
                if {"16", "65568"} <= set(m.split(","))
                and {"128", "192"} & set(m.split(","))}
    assert not per_head, per_head


#: sha256 (first 16 hex digits) of the optimised HLO of the qwen3-0.6b.decode
#: and olmoe-1b-7b.prefill cells' serving programs (prefill, decode) at their
#: long bucket, with op metadata and the source tables left out: the
#: programs as they were before latent attention, the leading dense layer
#: and the held-expert share joined the model code (their defaults must
#: leave these programs as they were)
SERVING_HLO = {
    ("qwen3_0_6b", "default"): ("88db2f99b52cb0d2", "c1be19814151399a"),
    ("qwen3_0_6b", "offload"): ("0ab54b6200f8ed2a", "c0005e5fb664f435"),
    ("olmoe_1b_7b", "default"): ("7efa6e448b4aad05", "b72ccdadaf32e19d"),
    ("olmoe_1b_7b", "offload"): ("4a3d2a9b30cb782a", "ba638036522a7847"),
}
_CELL_SHAPES = {"qwen3_0_6b": (32, 512, 128), "olmoe_1b_7b": (4, 2048, 16)}


def _digest(text: str) -> str:
    import hashlib

    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    kept = [ln for ln in text.splitlines() if not re.match(
        r"(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)", ln)]
    return hashlib.sha256("\n".join(kept).encode()).hexdigest()[:16]


@pytest.mark.parametrize("arch,plan_name", sorted(SERVING_HLO))
def test_serving_programs_of_the_earlier_cells_are_unchanged(
        one_chip, arch, plan_name):
    from repro.models.plan import ExecPlan

    plan = {"default": ExecPlan(), "offload": OFFLOAD_PLAN}[plan_name]
    batch, prompt, new = _CELL_SHAPES[arch]
    model, on, params, prefill, state = _lower_prefill(
        one_chip, get_config(arch), plan, batch, prompt, prompt + new)
    decode = jax.jit(lambda p, t, s: model.decode(p, t, s, plan),
                     donate_argnums=(2,))
    got = (_digest(prefill.compile().as_text()),
           _digest(decode.lower(params, _spec(one_chip, (batch, 1), jnp.int32),
                                state).compile().as_text()))
    assert got == SERVING_HLO[(arch, plan_name)]
