"""Planning driver: plans run back to back, each a first plan of one
program by the jaxpr frontend's GA, which measures candidates on the chip.

Traffic keys (``traffic/<mix>.json`` with ``"driver": "plan"``):

* ``program``: a module of ``programs/`` exposing a function of the same
  name and ``make_args``; ``batch``, ``seq`` (the rest of its shape comes
  from the configuration's widths);
* ``ga``: ``population``, ``generations`` and ``seed`` (plan ``i`` of a
  window uses ``seed + i``, so every run makes the same searches);
  ``repeats``: timed repeats per candidate;
* ``calls_per_plan``: back-to-back calls of the chosen program after each
  plan, timed together;
* ``trace_plans``: plans at the start of the window a traced run records.

Each plan starts cold: in-memory caches are cleared, the persistent
compilation cache is off inside the window, and no GA cache directory is
given (no seed bank, no measurement or surrogate journal).  A plan that
compiled nothing measured a cache, and the run stops.  After the window,
every plan's output is compared with the float32 reference.
"""
from __future__ import annotations

import importlib
import os
import time

import jax
from jax.experimental.compilation_cache import compilation_cache

from chipbench import common, reference, weights
from chipbench.flops import head_dim
from chipbench.harness import Context, Run, memory_peak_bytes


class MeasuredACache(RuntimeError):
    """A plan compiled nothing, so it timed a cache and not a first plan."""


def program(t: dict):
    mod = importlib.import_module(f"chipbench.programs.{t['program']}")
    return getattr(mod, t["program"]), mod.make_args


def make_args(ctx: Context):
    c, t = ctx.config, ctx.traffic
    _, make = program(t)
    return make(weights.key_from_seed(ctx.seed, 5), batch=t["batch"],
                seq=t["seq"], n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
                d_model=c["hidden_size"])


def _persistent_cache(on: bool) -> None:
    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def one_plan(ctx: Context, args, ga_seed: int, *, population: int,
             generations: int, traced: bool, span_path=None) -> dict:
    """Plan the program cold, then time its chosen artifact."""
    from repro.core import GAConfig, OffloadConfig, Offloader

    t = ctx.traffic
    fn, _ = program(t)
    jax.clear_caches()
    off = Offloader(OffloadConfig(
        frontend="jaxpr", repeats=t["repeats"], trace=span_path,
        ga=GAConfig(population=population, generations=generations,
                    seed=ga_seed),
        options={"example_args": args}))
    with common.compile_clock() as clock:
        t0 = time.perf_counter()
        with common.annotate("plan.prepare", traced):
            pctx = off.prepare(fn)
        t1 = time.perf_counter()
        with common.annotate("plan.search", traced):
            res = off.search(pctx)
        t2 = time.perf_counter()
    if clock["count"] == 0:
        raise MeasuredACache("a plan compiled nothing: it measured a "
                             "cache, not a first plan")
    call = jax.jit(res.artifact.fn)
    y = call(*args)
    y.block_until_ready()                  # loads the chosen program
    n = t["calls_per_plan"]
    with common.annotate("planned_call", traced):
        t3 = time.perf_counter()
        for _ in range(n):
            y = call(*args)
        y.block_until_ready()
        t4 = time.perf_counter()
    return {"t0": t0, "t1": t4, "plan_s": t2 - t0, "prepare_s": t1 - t0,
            "compile_s": clock["seconds"], "compiles": clock["count"],
            "evaluations": res.ga.evaluations, "calls": n,
            "call_s": (t4 - t3) / n, "output": y,
            "chosen": dict(res.report.substituted), "valid": res.best.valid}


def run(ctx: Context, tmp: str) -> Run:
    t = ctx.traffic
    out = Run(config=ctx.config, traffic=t, device_kind=ctx.device_kind)
    ga = t["ga"]
    args = make_args(ctx)
    # warm-up: one small plan, so the window's first plan pays no one-off
    # import or start-up cost that the others do not
    one_plan(ctx, args, ga["seed"], population=2, generations=1,
             traced=False)
    profile = common.Profile(tmp) if ctx.trace else None
    span_path = os.path.join(tmp, "spans.jsonl") if ctx.trace else None
    out.setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"set-up {out.setup_s:.3f} s")

    _persistent_cache(False)
    deadline = time.perf_counter() + ctx.seconds
    outputs = []
    try:
        if profile:
            profile.start()
        i = 0
        while time.perf_counter() < deadline:
            out.attempted += 1
            traced = profile is not None and profile.active
            try:
                p = one_plan(ctx, args, ga["seed"] + i,
                             population=ga["population"],
                             generations=ga["generations"], traced=traced,
                             span_path=span_path)
            except MeasuredACache:
                raise
            except Exception as e:  # noqa: BLE001 — a failed plan counts
                out.failed += 1
                ctx.log(f"plan {i} failed: {type(e).__name__}: {e}")
            else:
                outputs.append(p.pop("output"))
                out.plans.append(p)
                ctx.log(f"plan {i}: {p['plan_s']:.3f} s, {p['compiles']} "
                        f"compiles ({p['compile_s']:.3f} s), "
                        f"{p['evaluations']} evaluations, chose "
                        f"{p['chosen'] or 'reference'}; call "
                        f"{p['call_s'] * 1e3:.4f} ms")
            i += 1
            if profile and profile.active and i >= t["trace_plans"]:
                profile.stop()
    finally:
        if profile:
            profile.stop()
        _persistent_cache(True)
    if out.plans:
        out.window = (out.plans[0]["t0"], out.plans[-1]["t1"])
    out.memory_peak_bytes = memory_peak_bytes()
    ctx.log(f"window: {out.attempted} plans attempted, {len(out.plans)} "
            f"completed, {out.failed} failed; every plan compiled; "
            f"peak_bytes_in_use {out.memory_peak_bytes}")
    if profile:
        out.trace = profile.reduce()
    if span_path and os.path.exists(span_path):
        from repro.obs.trace import read_trace
        out.spans = read_trace(span_path)[0]
    out.checks, out.control = check(ctx, args, outputs)
    return out


def errors(ctx: Context, args, outputs: list, low: bool = False) -> list:
    """Widest relative L2 error (over rows) of each output against the
    float32 reference; ``low``: of the fp8 control instead."""
    ref = getattr(reference, ctx.traffic["program"] + "_jit")
    want = ref(*args)
    if low:
        outputs = [ref(*args, low=True)]
    return [float(reference.rel_l2(y, want).max()) for y in outputs]


def check(ctx: Context, args, outputs: list) -> tuple:
    """The checks ({name: (value, limit)}) and, where the context asks,
    the control's reading ({name: value})."""
    errs = errors(ctx, args, outputs)
    worst = max(errs) if errs else float("inf")
    ctx.log(f"check: {len(errs)} planned outputs vs the float32 reference: "
            f"widest relative L2 {worst!r} "
            f"(all: {[round(e, 6) for e in errs]})")
    control = {}
    if ctx.control:
        control["output_rel_l2"] = errors(ctx, args, [], low=True)[0]
        ctx.log(f"control (fp8 reference): relative L2 "
                f"{control['output_rel_l2']!r}")
    return {"output_rel_l2": (worst, ctx.limits["output_rel_l2"])}, control
