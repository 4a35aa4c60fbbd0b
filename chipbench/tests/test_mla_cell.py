"""The latent-attention cell on the CPU at a tiny size: its driver end to
end, the check failing a planted fault, and the new cells, traffic, limits,
driver and readers found by name with no earlier benchmark file changed."""
import copy
import json
import os
import tempfile

import pytest

import tiny
from chipbench import flops_mla, harness, trace as tr
from chipbench.drivers import serve, serve_mla

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, MESH4 = "deepseek-v2-lite.long-doc", "olmoe-1b-7b.plan-attn-mesh4"

with open(os.path.join(ROOT, "chipbench", "configs",
                       "deepseek-v2-lite.json")) as _f:
    MLA = json.load(_f)
#: the cell's configuration at CPU widths, 2 of 8 routed experts held
TINY_MLA = dict(MLA, hidden_size=64, intermediate_size=128,
                num_hidden_layers=3, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
                n_routed_experts=2, router_experts=8, held_first=2,
                num_experts_per_tok=2, vocab_size=256,
                assumed={"capacity_factor": 4.0})
LONG_DOC = dict(tiny.SERVE, driver="serve_mla", batch=1,
                prompt_lengths=[16, 32], check_rows=1)


def test_mla_driver_end_to_end():
    ctx = tiny.context(TINY_MLA, LONG_DOC, {"mean_logit_gap": 0.02})
    with tempfile.TemporaryDirectory() as d:
        run = serve_mla.run(ctx, d)
    assert run.correct, run.checks
    assert run.attempted == len(run.requests) > 2 and run.failed == 0
    assert run.compiles_in_window == 0
    assert {r["prompt"] for r in run.requests} == {16, 32}


def _narrow(**kw):
    """The cell at its published widths, cut to the dense layer and one
    MoE layer and a 2048-token vocabulary so that the CPU can run it."""
    traffic = dict(LONG_DOC, prompt_lengths=[16, 32], new_tokens=8)
    return tiny.context(dict(MLA, num_hidden_layers=2, vocab_size=2048),
                        traffic, tiny.cell_limits(CELL), **kw)


def test_narrowed_cell_is_correct_under_its_limits():
    with tempfile.TemporaryDirectory() as d:
        run = serve_mla.run(_narrow(seconds=0.2), d)
    assert run.correct, run.checks


def test_narrowed_cell_catches_an_altered_token(monkeypatch):
    from repro.runtime.serve import Server

    sample = Server._sample

    def altered(self, logits, key, i):
        tok = sample(self, logits, key, i)
        return (tok + 1) % logits.shape[-1] if i == 2 else tok

    monkeypatch.setattr(Server, "_sample", altered)
    with tempfile.TemporaryDirectory() as d:
        run = serve_mla.run(_narrow(seconds=0.2), d)
    assert not run.correct, run.checks


def test_narrowed_cell_catches_renormalised_gates():
    """The program told to renormalise its top-k gates (OLMoE's
    convention) against the published, unnormalised reference."""
    ctx = _narrow(seconds=0.2)
    params = serve_mla.make_weights(ctx.config, ctx.seed)
    lengths = serve.schedule(ctx.seed, ctx.traffic["prompt_lengths"], 2)
    cfg = serve_mla.arch_config(dict(ctx.config, norm_topk_prob=True))
    got = serve.gaps(ctx, params, _served(ctx, cfg, params, lengths),
                     lengths)
    good = serve.gaps(ctx, params, _served(
        ctx, serve_mla.arch_config(ctx.config), params, lengths), lengths)
    readings = {k: f(got) for k, f in serve.READINGS.items()}
    assert any(readings[k] > lim for k, lim in ctx.limits.items()), readings
    assert all(f(good) <= ctx.limits[k]
               for k, f in serve.READINGS.items() if k in ctx.limits)


def _served(ctx, cfg, params, lengths):
    """Two requests served by the program as ``cfg`` builds it."""
    from repro.models import REFERENCE_PLAN, build_model
    from repro.runtime.serve import ServeConfig, Server

    server = Server(build_model(cfg), params, REFERENCE_PLAN,
                    ServeConfig(max_new_tokens=ctx.traffic["new_tokens"]))
    return [(i, server.generate({"tokens": serve.prompt(
        ctx.seed, i, 1, lengths[i], ctx.config["vocab_size"])}))
        for i in range(2)]


def test_new_cells_are_found_by_name():
    bench = harness.load_benchmark(ROOT)
    w, c, t, limits = harness.load_cell(bench, CELL, ROOT)
    assert (w["chips"], t["driver"], c["program_arch"]) == \
        (1, "serve_mla", "deepseek_v2_lite")
    assert harness.driver(t["driver"]) is serve_mla
    assert set(limits) <= set(serve.READINGS)
    assert [m["name"] for m in harness.metrics_of(bench, CELL, False)] == \
        ["output_tokens_per_s", "request_p90_s", "setup_s"]
    traced = {m["name"] for m in harness.metrics_of(bench, CELL, True)}
    assert traced == {"serve.gap_ms", "device_idle_pct.serve",
                      "mla_serve_mfu_pct", "serve.mla_roofline_pct"}
    w4, c4, t4, l4 = harness.load_cell(bench, MESH4, ROOT)
    _, _, t1, l1 = harness.load_cell(bench, "olmoe-1b-7b.plan-attn", ROOT)
    assert w4["chips"] == 4 and l4 == l1
    assert {k: v for k, v in t4.items() if k != "batch"} == \
        {k: v for k, v in t1.items() if k != "batch"}
    assert t4["batch"] == 8
    traced4 = {m["name"] for m in harness.metrics_of(bench, MESH4, True)}
    assert "planned_mfu_pct" not in traced4 and "plan.prepare_s" in traced4
    assert "planned_mfu_pct.mesh4" in traced4
    assert "serve_mfu_pct" not in traced


def test_mla_readers_on_a_traced_run():
    """The new readers read a traced run's requests, window and recorded
    attention self time (between 0 and 100 where the times are plausible)
    and read nothing from an untraced run or another configuration's."""
    run = harness.Run(config=MLA, traffic=LONG_DOC, device_kind="TPU v5 lite")
    run.requests = [{"t0": 0.0, "t1": 2.0, "prompt": 65536, "batch": 1,
                     "new": 32},
                    {"t0": 2.0, "t1": 2.6, "prompt": 32768, "batch": 1,
                     "new": 32}]
    mfu = harness.reader("mla_serve_mfu_pct", ROOT)
    roof = harness.reader("serve.mla_roofline_pct", ROOT)
    assert mfu(run) is None and roof(run) is None
    run.trace = tr.Trace(window=(0, int(2.6e9)), devices={"d": [(0, 2.5e9)]},
                         annotations=[("request", 0, 2e9),
                                      ("request", 2e9, 2.6e9)])
    run.spans = [{"name": "region.attention", "dur_s": 1.6}]
    assert 0 < mfu(run) < 100 and 0 < roof(run) < 100
    other = copy.copy(run)
    other.config = tiny.MOE
    assert mfu(other) is None and roof(other) is None


def test_weight_layout_and_count_are_the_programs():
    """The driver's weight layout is the program's parameter tree for the
    cut, ``flops_mla`` counts its 902,062,592 parameters, and a
    configuration the program cannot serve as published is refused."""
    import jax
    from repro.models import build_model

    shapes = build_model(serve_mla.arch_config(MLA)).param_shapes()
    got = {tuple(k.key for k in path): leaf.shape for path, leaf in
           jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == {p: s for p, (s, _) in serve_mla.shapes(MLA).items()}
    assert flops_mla.param_count(MLA) == 902_062_592
    for bad in (dict(MLA, routed_scaling_factor=2.5),
                dict(MLA, rope_scaling=dict(MLA["rope_scaling"],
                                            mscale=1.0))):
        with pytest.raises(ValueError):
            serve_mla.arch_config(bad)


def test_four_chip_planned_mfu_counts_every_chip():
    """``planned_mfu_pct.mesh4`` divides by the chip time of every chip:
    a program sharded over four chips and one on a single chip of the four
    read the same share for the same work per chip-second, and the
    one-chip reader would read four times as much on the sharded one."""
    bench = harness.load_benchmark(ROOT)
    _, c, t, _ = harness.load_cell(bench, MESH4, ROOT)
    run = harness.Run(config=c, traffic=t, device_kind="TPU v5 lite")
    run.plans = [{"calls": 100}]
    mesh4 = harness.reader("planned_mfu_pct.mesh4", ROOT)
    one = harness.reader("planned_mfu_pct", ROOT)
    assert mesh4(run) is None
    span = [("window", 0, int(4e9)), ("planned_call", 0, int(4e9))]
    per_call = 4 * 8e-3               # chip-seconds a call
    sharded = {f"/device:TPU:{i}": [(0, 100 * per_call / 4 * 1e9)]
               for i in range(4)}
    single = {f"/device:TPU:{i}": [(0, 100 * per_call * 1e9)] if i == 0
              else [] for i in range(4)}
    run.trace = tr.Trace(window=(0, int(4e9)), devices=sharded,
                         annotations=span)
    a, a1 = mesh4(run), one(run)
    run.trace = tr.Trace(window=(0, int(4e9)), devices=single,
                         annotations=span)
    assert mesh4(run) == pytest.approx(a) and a1 == pytest.approx(4 * a)
    assert 0 < a < 100
