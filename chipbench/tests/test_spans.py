"""The span reduction (``spans.py``) on traces recorded on a TPU v5e:
``small.xplane.pb`` (``record_trace.py``: the harness's annotations round a
matmul chain, no program spans) and ``serve.xplane.pb``
(``record_serve_trace.py``: two reduced-size servers with the program's
profiler sink on).  Also the trace's per-layer metrics, pinned to what they
read on ``small.xplane.pb`` at the commit that added the reduction."""
import dataclasses
import json
import os

import pytest

from chipbench import spans
from chipbench import trace as tr
from chipbench.harness import ROOT, Run, reader

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
SERVE = os.path.join(DATA, "serve.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return spans.load(SMALL)


@pytest.fixture(scope="module")
def serve():
    return spans.load(SERVE)


# ---------------------------------------------------------------------------
# the trace's per-layer metrics read as before
# ---------------------------------------------------------------------------


def _pinned_run() -> Run:
    """``small.xplane.pb`` with its requests read 2 ms early (the device's
    clock runs ahead, ``test_trace.py``), the same spans also read as
    ``planned_call``s, and fixed requests and plans."""
    t = tr.load(SMALL)
    led = [(n, s - 2e6, e) for n, s, e in t.annotations if n == "request"]
    t = dataclasses.replace(t, annotations=sorted(
        [a for a in t.annotations if a[0] != "request"] + led
        + [("planned_call", s, e) for _, s, e in led], key=lambda a: a[1]))
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "olmoe-1b-7b.json")) as f:
        config = json.load(f)
    return Run(config=config,
               traffic={"program": "attention_block", "batch": 1,
                        "seq": 512},
               device_kind="TPU v5 lite", trace=t,
               requests=[{"t0": 0.0, "t1": 1.0, "prompt": 128, "batch": 2,
                          "new": 4}] * 3,
               plans=[{"calls": 4, "call_s": 1e-3, "plan_s": 1.0}] * 3)


@pytest.mark.parametrize("metric,value", [
    ("serve.gap_ms", 0.045722),
    ("serve_mfu_pct", 1.3862661659125677),
    ("device_idle_pct.serve", 98.61273708956608),
    ("device_idle_pct.plan", 98.61273708956608),
    ("planned_mfu_pct", 14.990864912710094),
])
def test_trace_metrics_read_as_before(metric, value):
    assert reader(metric)(_pinned_run()) == pytest.approx(value, rel=1e-12)


# ---------------------------------------------------------------------------
# a trace with the harness's annotations only
# ---------------------------------------------------------------------------


def test_harness_annotations_read_as_trace_py_reads_them(small):
    old = tr.load(SMALL)
    assert small.devices == old.devices
    assert small.spans == old.annotations
    assert small.named_gaps(*old.window) == old.named_gaps(10)


def test_ops_carry_module_and_op_name_and_self_time(small):
    (ops,) = small.ops.values()
    assert {o.module for o in ops} == {"jit__lambda"}
    assert {o.region for o in ops} == {spans.UNSCOPED}   # no named scopes
    names = spans.tf_ops(SMALL)["/device:TPU:0"]
    assert set(names.values()) == {"jit(<lambda>)/dot_general:"}
    # no op nests in another here: self time is the whole op
    assert all(o.self_ns == o.end - o.start for o in ops)
    top = small.top_ops(2)
    assert [n for n, _ in top] == ["jit__lambda/-/convolution_tanh_fusion",
                                   "jit__lambda/-/fusion"]


def test_region_is_the_innermost_named_scope():
    op = "jit(serve_decode)/while/body/closed_call/attention/kv_cache/dus"
    assert spans.region_of(op) == "kv_cache"
    assert spans.region_of("jit(f)/while/body/attention/norm/mul") == "norm"
    assert spans.region_of("jit(f)/while/body/dynamic_slice") == "-"


def test_self_time_subtracts_directly_nested_ops():
    ops = [spans.Op(0, 100, "while", "m", "-"),
           spans.Op(10, 40, "body.a", "m", "attention"),
           spans.Op(20, 30, "inner", "m", "kv_cache"),
           spans.Op(50, 90, "body.b", "m", "mlp"),
           spans.Op(120, 130, "after", "m", "head")]
    spans._self_times(ops)
    assert [o.self_ns for o in ops] == [30, 20, 10, 40, 10]
    # every device nanosecond counted once: self times sum to the union
    assert sum(o.self_ns for o in ops) == 110


def test_idle_inside_spans():
    busy = [(0, 10), (20, 30), (40, 50)]
    assert spans._idle(busy, [(5, 45)]) == [(10, 20), (30, 40)]
    assert spans._idle(busy, [(12, 18), (32, 60)]) == [(12, 18), (32, 40),
                                                      (50, 60)]
    assert spans._idle([], [(0, 5)]) == [(0, 5)]


def test_union_per_plan_counts_concurrent_spans_once():
    recs = [{"name": "plan.search", "t0": 0.0, "dur_s": 10.0},
            {"name": "plan.search", "t0": 20.0, "dur_s": 10.0},
            {"name": "eval.prepare", "t0": 1.0, "dur_s": 2.0},
            {"name": "eval.prepare", "t0": 2.0, "dur_s": 2.0},   # overlaps
            {"name": "eval.prepare", "t0": 21.0, "dur_s": 1.0},
            {"name": "eval.measure", "t0": 5.0, "dur_s": 1.0}]
    assert spans.union_per_plan(recs, "eval.prepare") == pytest.approx(2.0)
    assert spans.union_per_plan(recs, "eval.measure") == pytest.approx(0.5)
    assert spans.union_per_plan(recs, "ga.generation") is None
    assert spans.union_per_plan(recs[2:], "eval.prepare") is None
    run = Run(config={}, traffic={}, spans=recs)
    assert reader("plan.eval_prepare_s")(run) == pytest.approx(2.0)
    assert reader("plan.eval_measure_s")(run) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# a trace of the program's servers with the profiler sink on
# ---------------------------------------------------------------------------

REQUESTS, NEW = 3, 4                  # record_serve_trace.py


def test_program_spans_and_named_steps(serve):
    assert serve.count("request") == REQUESTS
    assert serve.count("serve.generate") == REQUESTS
    assert serve.count("serve.prefill") == REQUESTS
    assert serve.count("serve.decode_step") == REQUESTS * (NEW - 1)
    assert serve.count("serve.token_to_host") == REQUESTS * NEW
    assert serve.count("serve.sample") == REQUESTS * NEW
    (mods,) = serve.modules.values()
    names = [n for n, _, _ in mods]
    # one device execution per dispatch of each named step
    assert names.count("jit_serve_prefill") == REQUESTS
    assert names.count("jit_serve_decode") == REQUESTS * (NEW - 1)
    # each prefill's device time lies inside the request that dispatched
    # it, read with 2 ms of lead (the device's clock runs ahead)
    for s, e in serve.of("request"):
        inside = [n for n, a, b in mods if s - 2e6 <= a and b <= e]
        assert inside.count("jit_serve_prefill") == 1


def test_module_time_and_round_trip(serve):
    got = spans.serve_breakdown(serve)
    steps = REQUESTS * (NEW - 1)
    assert got["serve.decode_step_ms"] == pytest.approx(
        1e3 * serve.module_busy_s("jit_serve_decode") / steps)
    assert got["serve.prefill_ms"] == pytest.approx(
        1e3 * serve.module_busy_s("jit_serve_prefill") / REQUESTS)
    assert 0 < got["serve.decode_step_ms"] < got["serve.step_wall_ms"]
    # the round-trip spans tile the decode loop, so device time and idle
    # time inside them add up to the wall per step
    assert 0 < got["serve.round_trip_ms"] < got["serve.step_wall_ms"]
    per_step = got["serve.decode_step_ms"] + got["serve.round_trip_ms"]
    assert per_step == pytest.approx(got["serve.step_wall_ms"], rel=0.25)


def test_regions_self_time_counts_a_while_once(serve):
    for plane, ops in serve.ops.items():
        by_module = {}
        for o in ops:
            by_module.setdefault(o.module, 0)
            by_module[o.module] += o.self_ns
        busy = serve.devices[plane]
        assert sum(by_module.values()) == sum(e - s for s, e in busy)
        assert any(o.name.startswith("while") for o in ops
                   if o.module == "jit_serve_decode")
    regions = spans.serve_breakdown(serve)["regions_ms"]
    for r in ("attention", "kv_cache", "mlp", "norm", "head", "embed"):
        assert regions[r] > 0, r
    assert regions["moe"] > 0                     # the OLMoE server
    assert serve.region_self_s("moe", "jit_serve_decode") > 0
    assert serve.region_self_s("kv_cache", "jit_serve_decode") > 0


def test_idle_gaps_inside_a_request_are_named_by_program_spans(serve):
    for g0, g1 in serve.of("serve.generate"):
        for name, secs in serve.named_gaps(g0, g1, top=5):
            assert name.startswith("serve."), name
    window = serve.of("window")[0]
    names = [n for n, _ in serve.named_gaps(*window, top=3)]
    assert names == ["window"] * 3                # the 20 ms host sleeps


def test_moe_roofline_share_reads_the_moe_region(serve):
    # the OLMoE layout at ArchConfig.reduced(): 2 layers, width 64, 4
    # experts of width 64, top-2; one request of 2 x 16 prompt tokens and
    # 3 decoded tokens
    c = {"hidden_size": 64, "intermediate_size": 64, "num_experts": 4,
         "num_experts_per_tok": 2, "num_hidden_layers": 2}
    per_token = 2 * 64 * 4 + 2 * 3 * 2 * 64 * 64
    assert spans.routed_expert_flops(c, 2, 16, 4) == 2 * 19 * 2 * per_token
    req = {"batch": 2, "prompt": 16, "new": 4}
    got = spans.serve_breakdown(serve, c, [req], "TPU v5 lite")
    want = 100.0 * spans.routed_expert_flops(c, 2, 16, 4) \
        / serve.region_self_s("moe") / 197e12
    assert got["serve.moe_roofline_pct"] == pytest.approx(want)
    assert 0 < want < 100
