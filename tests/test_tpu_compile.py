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
