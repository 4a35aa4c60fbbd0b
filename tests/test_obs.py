"""Observability-layer tests: span nesting/threading, the disabled no-op
path, metrics snapshot round-trip + Prometheus rendering, the e2e
``Offloader.plan`` trace across every registered frontend (phase spans must
account for >= 90% of the plan wall), the obsreport renderer, the
pattern-precision journal, and the plan-store TTL sweep.
"""
import json
import threading

import pytest

from repro.core import GAConfig, OffloadConfig, Offloader
from repro.core.pattern_db import (PatternDB, load_pattern_precision,
                                   record_pattern_outcome)
from repro.launch.obsreport import render
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from test_offload_api import ALL_FRONTENDS, FRONTEND_CASES, _config, _ir_graph


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_counter_gauge_histogram_roundtrip():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("hits", kind="a").inc()
    reg.counter("hits", kind="a").inc(2)         # same handle re-resolved
    reg.counter("hits", kind="b").inc(5)
    reg.gauge("level").set(1.5)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)

    snap = reg.snapshot()
    json.loads(json.dumps(snap))                 # plain-JSON round trip
    by_labels = {tuple(s["labels"].items()): s["value"]
                 for s in snap["hits"]["series"]}
    assert by_labels == {(("kind", "a"),): 3.0, (("kind", "b"),): 5.0}
    assert snap["level"]["series"][0]["value"] == 1.5
    hs = snap["lat"]["series"][0]
    assert hs["count"] == 3
    assert hs["sum"] == pytest.approx(5.55)
    assert hs["min"] == 0.05 and hs["max"] == 5.0
    # cumulative le buckets: 0.05 <= 0.1; 0.5 <= 1.0; 5.0 only in +Inf
    assert hs["buckets"] == {"0.1": 1, "1": 2}

    text = reg.render_prometheus()
    assert '# TYPE hits counter' in text
    assert 'hits{kind="a"} 3' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert 'lat_count 3' in text

    reg.reset()
    assert reg.snapshot() == {}


def test_metric_name_is_bound_to_one_kind():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(ValueError):
        reg.gauge("x")


def test_metrics_concurrent_increments_are_lossless():
    reg = obs_metrics.MetricsRegistry()

    def work():
        for _ in range(1000):
            reg.counter("n").inc()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("n").value == 4000


# ---------------------------------------------------------------------------
# tracing: disabled no-op, nesting, threading, file round-trip
# ---------------------------------------------------------------------------


def test_disabled_tracing_is_a_shared_noop():
    assert obs_trace.active_tracer() is None
    s = obs_trace.span("anything", attr=1)
    assert s is obs_trace.NULL_SPAN              # no allocation per call
    with s as inner:
        assert inner.set(more=2) is inner
    assert obs_trace.current_span_id() is None


def test_span_nesting_and_parentage(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    obs_trace.enable(path, flush_every=1)
    try:
        with obs_trace.span("root") as root:
            with obs_trace.span("child") as child:
                assert child.parent == root.id
                assert obs_trace.current_span_id() == child.id
                with obs_trace.span("grandchild", depth=2) as g:
                    assert g.parent == child.id
            assert obs_trace.current_span_id() == root.id
        with obs_trace.span("sibling"):
            pass
    finally:
        obs_trace.disable()

    spans, snap = obs_trace.read_trace(path)
    by_name = {s["name"]: s for s in spans}
    assert by_name["grandchild"]["parent"] == by_name["child"]["id"]
    assert by_name["child"]["parent"] == by_name["root"]["id"]
    assert by_name["root"]["parent"] is None
    assert by_name["sibling"]["parent"] is None
    assert by_name["grandchild"]["attrs"] == {"depth": 2}
    assert all(s["dur_s"] >= 0 for s in spans)
    assert snap is not None                      # close() appended metrics


def test_spans_nest_per_thread_with_explicit_cross_thread_parent(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    obs_trace.enable(path)
    try:
        with obs_trace.span("dispatch") as d:
            parent = obs_trace.current_span_id()

            def worker(tag, explicit):
                # a fresh thread has its own empty stack: no implicit
                # parent leaks across threads
                kw = {"parent": explicit} if explicit else {}
                with obs_trace.span(f"work-{tag}", **kw):
                    pass

            threads = [threading.Thread(target=worker,
                                        args=("wired", parent)),
                       threading.Thread(target=worker, args=("free", None))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    finally:
        obs_trace.disable()
    by_name = {s["name"]: s for s in obs_trace.read_trace(path)[0]}
    assert by_name["work-wired"]["parent"] == by_name["dispatch"]["id"]
    assert by_name["work-free"]["parent"] is None


def test_maybe_tracing_is_idempotent(tmp_path):
    outer = str(tmp_path / "outer.jsonl")
    inner = str(tmp_path / "inner.jsonl")
    with obs_trace.maybe_tracing(outer) as t1:
        with obs_trace.maybe_tracing(inner) as t2:   # already active: no-op
            assert t2 is t1
            with obs_trace.span("s"):
                pass
    assert obs_trace.active_tracer() is None
    assert not (tmp_path / "inner.jsonl").exists()
    spans, _ = obs_trace.read_trace(outer)
    assert [s["name"] for s in spans] == ["s"]
    with obs_trace.maybe_tracing(None) as t:
        assert t is None                             # falsy path: disabled
        assert obs_trace.span("x") is obs_trace.NULL_SPAN


def test_error_inside_span_is_recorded_and_reraised(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with pytest.raises(RuntimeError):
        with obs_trace.maybe_tracing(path):
            with obs_trace.span("boom"):
                raise RuntimeError("nope")
    spans, _ = obs_trace.read_trace(path)
    assert spans[0]["attrs"]["error"] == "RuntimeError"


# ---------------------------------------------------------------------------
# e2e: Offloader.plan emits the phase spans on every frontend
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# the profiler sink: spans on the jax profiler's clock
# ---------------------------------------------------------------------------


def _host_events(profile_dir) -> dict:
    """{(plane, line index): [(event name, start_ns, end_ns)]} of the host
    planes of the one ``.xplane.pb`` under ``profile_dir`` (a line per
    thread; threads may share a line name)."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out[plane.name, i] = [(e.name, e.start_ns, e.end_ns)
                                      for e in line.events]
    return out


def _profiled(tmp_path, body) -> dict:
    import jax

    obs_trace.enable_profiler()
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
        obs_trace.disable_profiler()
    return _host_events(tmp_path / "profile")


def test_profiler_sink_puts_nested_spans_on_host_planes(tmp_path):
    def body():
        with obs_trace.span("outer", ignored=1) as sp:
            assert sp.set(more=2) is sp and sp.id is None
            with obs_trace.span("inner"):
                pass
        worker = threading.Thread(target=lambda: obs_trace.span(
            "on_worker").__enter__().__exit__(None, None, None))
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()

    lines = _profiled(tmp_path, body)
    where = {n: (line, s, e) for line, evs in lines.items()
             for n, s, e in evs if n in ("outer", "inner", "on_worker")}
    assert set(where) == {"outer", "inner", "on_worker"}
    outer, inner = where["outer"], where["inner"]
    assert outer[0] == inner[0]                  # one thread, one line
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    assert where["on_worker"][0] != outer[0]     # the worker's own line


def test_profiler_and_jsonl_sinks_record_together(tmp_path):
    path = str(tmp_path / "trace.jsonl")

    def body():
        obs_trace.enable(path, flush_every=1)
        try:
            with obs_trace.span("both") as sp:
                with obs_trace.span("both.child") as child:
                    assert child.parent == sp.id
        finally:
            obs_trace.disable()

    lines = _profiled(tmp_path, body)
    names = {n for evs in lines.values() for n, _, _ in evs}
    assert {"both", "both.child"} <= names
    spans, _ = obs_trace.read_trace(path)
    assert [s["name"] for s in spans] == ["both.child", "both"]


def test_profiler_sink_off_is_the_null_span_again():
    obs_trace.enable_profiler()
    assert obs_trace.span("x") is not obs_trace.NULL_SPAN
    obs_trace.disable_profiler()
    assert obs_trace.span("x") is obs_trace.NULL_SPAN
    assert obs_trace.active_tracer() is None


def test_obs_imports_no_jax_until_the_profiler_sink(tmp_path):
    import subprocess
    import sys

    code = f"""
import sys
from repro.launch.obsreport import render
from repro.obs import trace
path = {str(tmp_path / "t.jsonl")!r}
trace.enable(path)
with trace.span("a"):
    pass
trace.disable()
render(trace.read_trace(path)[0])
assert "jax" not in sys.modules, "the JSONL path imported jax"
trace.enable_profiler()
assert "jax.profiler" in sys.modules
"""
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]


@pytest.mark.parametrize("name", ALL_FRONTENDS)
def test_plan_trace_covers_phases_on_every_frontend(name, tmp_path):
    target, inputs, kwargs = FRONTEND_CASES[name]()
    path = str(tmp_path / "trace.jsonl")
    cfg = _config(kwargs, trace=path,
                  ga=GAConfig(population=6, generations=2, seed=0))
    Offloader(cfg).plan(target, inputs)
    assert obs_trace.active_tracer() is None     # plan closed its tracer

    spans, snap = obs_trace.read_trace(path)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    root = by_name["offload.plan"][0]
    assert root["parent"] is None
    phases = [s for phase in ("plan.prepare", "plan.search")
              for s in by_name[phase]]
    assert all(p["parent"] == root["id"] for p in phases)
    # the timeline accounts for the plan wall: prepare + search are the
    # only direct children and cover >= 90% of the root span
    covered = sum(p["dur_s"] for p in phases)
    assert covered >= 0.90 * root["dur_s"]
    # apply nests under search; the GA's generations under search too
    assert by_name["plan.apply"][0]["parent"] == by_name["plan.search"][0]["id"]
    assert len(by_name["ga.generation"]) == 2
    assert by_name["eval.batch"], "evaluator batches must be spanned"
    # the metrics snapshot rode along in the same file
    assert snap is not None and "ga.generations" in snap

    report = render(spans, snap)
    assert "offload.plan" in report and "plan.search" in report
    assert "coverage:" in report and "metrics:" in report


def test_plan_without_trace_writes_nothing(tmp_path):
    cfg = OffloadConfig(ga=GAConfig(population=4, generations=1, seed=0))
    assert cfg.trace is None
    Offloader(cfg).plan(_ir_graph())
    assert obs_trace.active_tracer() is None


# ---------------------------------------------------------------------------
# obsreport renderer
# ---------------------------------------------------------------------------


def test_obsreport_render_orphans_and_metrics():
    spans = [
        {"kind": "span", "trace": "t-x", "id": 1, "parent": None,
         "name": "root", "t0": 0.0, "dur_s": 1.0, "ts": 0.0, "attrs": {}},
        {"kind": "span", "trace": "t-x", "id": 2, "parent": 1,
         "name": "half", "t0": 0.1, "dur_s": 0.5, "ts": 0.0,
         "attrs": {"k": "v"}},
        # parent id 99 never finished (crash): rendered as a root, not lost
        {"kind": "span", "trace": "t-x", "id": 3, "parent": 99,
         "name": "orphan", "t0": 0.2, "dur_s": 0.1, "ts": 0.0, "attrs": {}},
    ]
    out = render(spans, {"c": {"kind": "counter",
                               "series": [{"labels": {}, "value": 2.0}]}})
    assert "spans=3 roots=2" in out
    assert "orphan" in out and "k=v" in out
    assert "account for 50.0% of root wall" in out
    assert "c" in out and "counter" in out


def test_serve_span_tree_and_its_obsreport(tmp_path):
    """One traced ``generate`` from a stored plan: the store's spans, then
    prefill and, per token, sample / token_to_host / decode_step under
    ``serve.generate``; obsreport renders the tree."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models import REFERENCE_PLAN, build_model
    from repro.runtime.serve import ServeConfig, Server
    from repro.service import PlanRecord, PlanStore

    cfg = get_config("qwen3_0_6b").reduced()
    model = build_model(cfg)
    store = PlanStore(str(tmp_path / "plans"))
    store.put(PlanRecord(
        fingerprint="fp", frontend="module", version=0, bits=(), sites=(),
        destinations=(), pattern={}, best_time_s=1.0, baseline_time_s=1.0,
        verified=True,
        payload={"exec_plan": {
            k: v for k, v in dataclasses.asdict(REFERENCE_PLAN).items()
            if isinstance(v, (str, int, float, bool))}}))
    path = str(tmp_path / "serve.jsonl")
    new = 3
    with obs_trace.maybe_tracing(path):
        server = Server.from_store(model, model.init(jax.random.key(0)),
                                   store, "fp",
                                   ServeConfig(max_new_tokens=new))
        server.generate({"tokens": jax.numpy.ones((2, 8), "int32")})
    spans, _ = obs_trace.read_trace(path)
    names = [s["name"] for s in spans]
    assert names.count("store.load") == 1
    assert names.count("store.rehydrate") == 1
    (gen,) = [s for s in spans if s["name"] == "serve.generate"]
    kids = sorted((s for s in spans if s["parent"] == gen["id"]),
                  key=lambda s: s["t0"])
    assert [s["name"] for s in kids] == (
        ["serve.prefill", "serve.sample"]
        + ["serve.token_to_host", "serve.decode_step", "serve.sample"]
        * (new - 1) + ["serve.token_to_host"])
    out = render(spans)
    for name in ("serve.generate", "serve.prefill", "serve.decode_step",
                 "serve.sample", "serve.token_to_host", "store.load"):
        assert name in out


# ---------------------------------------------------------------------------
# pattern precision journal
# ---------------------------------------------------------------------------


def test_pattern_precision_journal_and_accessor(tmp_path):
    d = str(tmp_path)
    for outcome in ("ok", "ok", "ok", "verify_fail", "bind_fail"):
        record_pattern_outcome(d, "matmul", "pallas", outcome, region="r0")
    record_pattern_outcome(d, "scan", "pallas", "error")
    record_pattern_outcome(d, None, "pallas", "ok")      # dropped: no pattern
    record_pattern_outcome(None, "ghost", "pallas", "ok")  # metrics-only

    counts = load_pattern_precision(d)
    assert counts["matmul"] == {"ok": 3, "verify_fail": 1, "bind_fail": 1}
    assert "ghost" not in counts

    db = PatternDB([], precision_dir=d)
    # bind_fail is excluded from the denominator: 3 ok / 4 ran
    assert db.precision("matmul") == pytest.approx(0.75)
    assert db.precision("scan") == pytest.approx(0.0)
    assert db.precision("never-seen") is None            # no evidence
    assert PatternDB([]).precision("matmul") is None     # no journal dir
    # explicit cache_dir overrides the constructor default
    assert PatternDB([]).precision("matmul", cache_dir=d) == \
        pytest.approx(0.75)


def test_measured_jaxpr_plan_journals_pattern_outcomes(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    # the linear-recurrence shape the kernel registry can actually bind —
    # a substituted chromosome is a distinct phenotype, so the search
    # measures it and its verifier verdict reaches the journal
    def rec_app(la, b):
        def step(h, ab):
            h = jnp.exp(ab[0]) * h + ab[1]
            return h, h
        _, hs = jax.lax.scan(step, jnp.zeros(la.shape[-1]), (la, b))
        return hs * 1.5

    r = np.random.default_rng(0)
    la = -jnp.abs(jnp.asarray(r.random((12, 8), dtype=np.float32))) * 0.2
    b = jnp.asarray(r.random((12, 8), dtype=np.float32)) * 0.5
    cache = str(tmp_path / "cache")
    cfg = OffloadConfig(
        options={"example_args": (la, b)}, repeats=1,
        ga=GAConfig(population=6, generations=2, seed=0, cache_dir=cache))
    res = Offloader(cfg).plan(rec_app)
    assert res.frontend == "jaxpr"

    counts = load_pattern_precision(cache)
    assert "linear_recurrence" in counts
    assert sum(counts["linear_recurrence"].values()) >= 1
    assert set(counts["linear_recurrence"]) <= set("ok verify_fail error "
                                                   "bind_fail".split())
    p = PatternDB([], precision_dir=cache).precision("linear_recurrence")
    assert p is not None and 0.0 <= p <= 1.0


# ---------------------------------------------------------------------------
# store TTL eviction
# ---------------------------------------------------------------------------


def test_store_evict_stale_drops_old_keeps_live(tmp_path):
    import dataclasses as dc

    from repro.service import PlanStore
    from test_service import _store_record

    store = PlanStore(str(tmp_path))
    ctx, rec = _store_record(tmp_path)
    old = store.put(rec)
    other = store.put(dc.replace(rec, fingerprint="fp-other"))
    kept = store.put(dc.replace(rec, fingerprint="fp-kept"))

    now = max(old.ts, other.ts, kept.ts) + 100.0
    # everything is older than 50s, but "fp-kept" is pinned
    evicted = store.evict_stale(50.0, now=now, keep={"fp-kept"})
    assert evicted == tuple(sorted({ctx.fingerprint, "fp-other"}))
    assert store.load(ctx.fingerprint) is None
    assert store.load("fp-other") is None
    assert store.load("fp-kept").version == kept.version
    # unpinned, the survivor is stale too
    assert store.evict_stale(50.0, now=now) == ("fp-kept",)
    assert store.fingerprints() == ()
    # an empty store sweep is a no-op
    assert store.evict_stale(1e6) == ()


def test_service_evict_stale_counts_and_spares_deployed(tmp_path):
    import dataclasses as dc

    from repro.service import PlanService, PlanStore, record_from_result
    from test_service import _ir_config

    with PlanService(str(tmp_path), config=_ir_config()) as svc:
        served = svc.plan(_ir_graph())           # deployed: must survive
        # plant a second, retired fingerprint directly in the store
        retired = svc.store.put(
            dc.replace(served.record, fingerprint="fp-retired"))
        now = retired.ts + 100.0
        evicted = svc.evict_stale(50.0, now=now)
        assert evicted == ("fp-retired",)
        assert svc.stats.evictions == 1
        assert svc.store.load(served.fingerprint) is not None
        assert svc.current(served.fingerprint) is served
        assert svc.stats.as_dict()["evictions"] == 1
