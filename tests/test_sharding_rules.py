"""Sharding rules: logical-axis resolution, divisibility fallbacks, param
pattern matching.  Uses a stub mesh (rules.pspec is pure — no devices)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import build_model
from repro.runtime import sharding as shd
from repro.runtime.pspec import ShardingRules


class StubMesh:
    def __init__(self, shape: dict):
        self.shape = shape


def _rules(shape=None):
    mesh = StubMesh(shape or {"data": 16, "model": 16})
    return ShardingRules(mesh, shd.logical_table(mesh))  # type: ignore


def test_divisible_dim_shards():
    r = _rules()
    assert r.pspec((32000, 2048), ("vocab", "fsdp")) == P("model", "data")


def test_non_divisible_dim_replicates():
    r = _rules()
    # 51865 % 16 != 0 -> vocab axis dropped (whisper's vocab)
    assert r.pspec((51865, 768), ("vocab", "fsdp")) == P(None, "data")


def test_axis_used_once():
    r = _rules()
    # both dims ask for "model": second one must drop
    spec = r.pspec((1024, 2048), ("vocab", "tensor"))
    assert spec == P("model", None)


def test_multi_axis_batch():
    mesh = StubMesh({"pod": 2, "data": 16, "model": 16})
    r = ShardingRules(mesh, shd.logical_table(mesh))  # type: ignore
    assert r.pspec((256, 128), ("batch", None)) == P(("pod", "data"), None)
    # batch=8 divides pod(2) but not pod*data(32): partial prefix kept
    assert r.pspec((8, 128), ("batch", None)) == P("pod", None)
    # batch=1: fully replicated
    assert r.pspec((1, 128), ("batch", None)) == P(None, None)


def test_param_axes_head_divisibility():
    class M:
        shape = {"data": 16, "model": 16}
    cfg = get_config("tinyllama_1_1b")  # 32 q heads (div), 4 kv heads (not)
    model = build_model(cfg)
    shapes = model.param_shapes()
    axes = shd.param_logical_axes(shapes, cfg, M())  # type: ignore
    assert axes["blocks"]["attn"]["wq"] == (None, "fsdp", "tensor")
    assert axes["blocks"]["attn"]["wk"] == (None, "fsdp", None)
    assert axes["blocks"]["attn"]["wo"] == (None, "tensor", "fsdp")

    cfg2 = get_config("gemma_7b")  # 16 heads == mesh: both shard
    m2 = build_model(cfg2)
    axes2 = shd.param_logical_axes(m2.param_shapes(), cfg2, M())  # type: ignore
    assert axes2["blocks"]["attn"]["wk"] == (None, "fsdp", "tensor")


def test_moe_expert_sharding():
    class M:
        shape = {"data": 16, "model": 16}
    cfg = get_config("olmoe_1b_7b")
    model = build_model(cfg)
    axes = shd.param_logical_axes(model.param_shapes(), cfg, M())  # type: ignore
    assert axes["blocks"]["moe"]["w_gate"] == (None, "experts", "fsdp", None)
    assert axes["blocks"]["moe"]["w_down"] == (None, "experts", None, "fsdp")


def test_state_axes_kv_fallback():
    class M:
        shape = {"data": 16, "model": 16}

        def __contains__(self, x):
            return x in self.shape
    cfg = get_config("tinyllama_1_1b")  # kv=4: not divisible -> shard seq
    ax = shd._axes_for_state("kv/k", (22, 2, 4, 32768, 64), cfg, M())  # type: ignore
    assert ax == (None, "batch", None, "kv_seq", None)
    cfg2 = get_config("gemma_7b")  # kv=16: divisible -> shard heads
    ax2 = shd._axes_for_state("kv/k", (28, 2, 16, 32768, 256), cfg2, M())  # type: ignore
    assert ax2 == (None, "batch", "kv_heads", None, None)
