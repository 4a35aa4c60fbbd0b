"""Smoke run of the offloader's main path on one chip: plan -> store -> serve.

Three phases, one per source language, each driven through the entry points
a user calls and each checked against a plain reference:

* ``model_phase`` — a model config at its published widths (random bf16
  weights from a seed) planned by the module frontend with compiled-artifact
  fitness, stored in a :class:`PlanStore`, served by ``Server.from_store``
  and checked, logit by logit, against a float32 ``REFERENCE_PLAN`` forward
  teacher-forced on the served tokens;
* ``jaxpr_phase`` — a hand-written GQA attention + rmsnorm function planned
  by the jaxpr frontend with wall-clock fitness, then run with both sites
  forced onto their Pallas kernels: a kernel that falls back or fails to
  compile fails the phase;
* ``python_phase`` — a numeric loop program planned by the python_ast
  frontend, its chosen variant executed.

``mesh_phase`` (four devices) plans the same jaxpr function with mesh
destinations and runs the sharded offload-plan loss, each against its
single-device float32 reference.

Every phase raises :class:`SmokeFailure` when a check fails.  Timings are
smoke figures, not a benchmark.  The chip_smoke.py script at the checkout
root is the command-line entry point.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import tempfile
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

SMOKE_NOTE = "smoke, not a benchmark"

#: relative L2 error allowed per output row for bf16 against float32
BF16_REL_L2 = 2e-2

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def rel_l2(got, want) -> np.ndarray:
    """Relative L2 error over the last axis: one value per row."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    num = np.linalg.norm((got - want).reshape(-1, want.shape[-1]), axis=-1)
    den = np.linalg.norm(want.reshape(-1, want.shape[-1]), axis=-1)
    return num / np.maximum(den, 1e-30)


@contextlib.contextmanager
def compile_clock():
    """Yields a dict whose ``seconds`` / ``count`` sum the backend compiles
    that ran inside the block (a persistent-cache hit compiles nothing)."""
    acc = {"seconds": 0.0, "count": 0}

    def listen(event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            acc["seconds"] += duration
            acc["count"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield acc
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float32)
        if jnp.issubdtype(w.dtype, jnp.floating) else w, tree)


# ---------------------------------------------------------------------------
# (a) model phase: module frontend -> PlanStore -> Server.from_store
# ---------------------------------------------------------------------------


def model_phase(cfg, *, device_kind: str, batch: int = 4, prompt: int = 128,
                new_tokens: int = 32, requests: int = 3, seed: int = 0,
                population: int = 4, generations: int = 2,
                tol: float = BF16_REL_L2,
                log: Callable[[str], None] = print) -> dict:
    """Plan, store and serve ``cfg``; check the served logits."""
    from repro import roofline as rl
    from repro.core import GAConfig, OffloadConfig, Offloader
    from repro.models import REFERENCE_PLAN, build_model
    from repro.models import transformer as T
    from repro.runtime.serve import ServeConfig, Server
    from repro.service import PlanStore, record_from_result

    require(cfg.family in ("dense", "moe"),
            f"model phase serves decoder-only models, not {cfg.family}")
    model = build_model(cfg)
    params = jax.jit(functools.partial(model.init, dtype=jnp.bfloat16))(
        jax.random.key(seed))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    log(f"model: arch={cfg.arch_id} params={n_params} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"vocab={cfg.vocab} dtype=bfloat16")

    cap = prompt + new_tokens
    param_specs = jax.eval_shape(lambda: params)
    token_specs = {"tokens": jax.ShapeDtypeStruct((batch, prompt), jnp.int32)}

    def lower_fn(plan):
        # the served prefill, exactly as Server will jit it, for this chip
        return jax.jit(lambda p, inp: model.prefill(
            p, inp, plan, cache_capacity=cap)).lower(param_specs, token_specs)

    ocfg = OffloadConfig(
        frontend="module",
        ga=GAConfig(population=population, generations=generations,
                    seed=seed),
        options={"lower_fn": lower_fn, "n_devices": 1,
                 "device_kind": device_kind,
                 "model_flops": rl.model_flops_infer(
                     cfg.param_count(active_only=True), batch * prompt)})
    off = Offloader(ocfg)
    with compile_clock() as plan_compiles:
        t0 = time.perf_counter()
        ctx = off.prepare(cfg)            # Offloader.plan == search(prepare)
        res = off.search(ctx)
        plan_s = time.perf_counter() - t0
    require(res.best.valid and math.isfinite(res.best.time_s),
            f"no valid plan: {res.best.detail}")
    log(f"model: plan sites={list(ctx.sites)} bits={list(res.best.bits)} "
        f"roofline_step_s={res.best.time_s:.6g} (estimate) "
        f"baseline_step_s={res.baseline.time_s:.6g} (estimate)")

    with tempfile.TemporaryDirectory(prefix="smoke_plan_store_") as d:
        store = PlanStore(d)
        stored = store.put(record_from_result(res, ctx.fingerprint,
                                              meta={"smoke": True}))
        server = Server.from_store(model, params, store, ctx.fingerprint,
                                   ServeConfig(max_new_tokens=new_tokens))
    require(server.plan == res.artifact,
            "Server.from_store rehydrated a different plan than was searched")
    knobs = {f: getattr(server.plan, f) for f, _, _ in
             server.plan.OFFLOAD_SITES}
    log(f"model: stored version={stored.version} fingerprint="
        f"{ctx.fingerprint[:16]}; serving {knobs}")

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (requests, batch, prompt),
                           dtype=np.int32)
    served, wall = [], []
    with compile_clock() as serve_compiles:
        for r in range(requests):
            t0 = time.perf_counter()
            toks, logits = server.generate({"tokens": jnp.asarray(prompts[r])},
                                           return_logits=True)
            logits.block_until_ready()
            wall.append(time.perf_counter() - t0)
            served.append((toks, logits))
    for toks, logits in served:
        require(toks.shape == (batch, new_tokens), f"tokens {toks.shape}")
        require(logits.shape == (batch, new_tokens, cfg.vocab),
                f"logits {logits.shape}")
        require(bool(jnp.all(jnp.isfinite(logits))), "non-finite logits")
        # greedy serving: every token is the argmax of its own logits
        require(np.array_equal(toks, np.asarray(jnp.argmax(logits, -1))),
                "served tokens are not the argmax of the served logits")

    # float32 reference on the same weights, teacher-forced on the served
    # tokens: position prompt-1+i predicts served token i
    ref_plan = REFERENCE_PLAN.replace(compute_dtype="float32")
    ref_params = _f32(params)

    @jax.jit
    def ref_logits(p, tokens):
        x = T.embed_inputs(p, cfg, ref_plan, tokens)
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        hidden, _, _ = T.forward_full(p, x, cfg, ref_plan, pos)
        return T.lm_logits(p, cfg, ref_plan, hidden[:, prompt - 1:])

    errs = []
    with jax.default_matmul_precision("highest"):
        for r, (toks, logits) in enumerate(served):
            seq = np.concatenate([prompts[r], toks[:, :-1]], axis=1)
            want = ref_logits(ref_params, jnp.asarray(seq))
            errs.append(rel_l2(logits, want))
    err = np.concatenate(errs)
    max_err = float(err.max())
    require(max_err <= tol, f"served logits off the float32 reference: "
                            f"max rel L2 {max_err:.4g} > {tol}")
    out = {
        "arch": cfg.arch_id, "params": n_params,
        "plan": {k: str(v) for k, v in res.pattern.items()},
        "plan_s": plan_s, "plan_compiles": plan_compiles["count"],
        "plan_compile_s": plan_compiles["seconds"],
        "serve_compiles": serve_compiles["count"],
        "serve_compile_s": serve_compiles["seconds"],
        "generate_s": wall, "max_rel_l2": max_err,
        "mean_rel_l2": float(err.mean()), "tol": tol,
    }
    log(f"model: compile_s plan={plan_compiles['seconds']:.3f} "
        f"({plan_compiles['count']} compiles) serve="
        f"{serve_compiles['seconds']:.3f} ({serve_compiles['count']} "
        f"compiles) plan_s={plan_s:.3f} ({SMOKE_NOTE})")
    log(f"model: generate_s={[round(w, 4) for w in wall]} "
        f"({SMOKE_NOTE}; the first includes compiles)")
    log(f"model: served logits vs float32 reference: max rel L2 "
        f"{max_err:.4g} mean {out['mean_rel_l2']:.4g} (tol {tol}) over "
        f"{err.size} positions")
    return out


# ---------------------------------------------------------------------------
# (b) jaxpr phase: traced attention + rmsnorm -> Pallas kernels
# ---------------------------------------------------------------------------


@jax.jit
def attention(q, k, v):
    """Causal GQA softmax attention: q (B,S,Hq,D), k/v (B,S,Hkv,D)."""
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@jax.jit
def rmsnorm(x, scale):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + 1e-6)
            * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def attention_block(x, scale, wo, q, k, v):
    """Attention, output projection, residual add, rmsnorm."""
    b, s = q.shape[:2]
    o = attention(q, k, v).reshape(b, s, -1)
    return rmsnorm(x + o @ wo, scale)


def attention_block_args(*, batch: int, seq: int, n_heads: int,
                         n_kv_heads: int, head_dim: int, d_model: int,
                         seed: int, dtype=jnp.bfloat16) -> tuple:
    """Seeded inputs of :func:`attention_block`, made on the device."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (batch, seq, d_model), jnp.float32)
    scale = 0.1 * jax.random.normal(ks[1], (d_model,), jnp.float32)
    wo = jax.random.normal(ks[2], (n_heads * head_dim, d_model),
                           jnp.float32) / math.sqrt(n_heads * head_dim)
    q = jax.random.normal(ks[3], (batch, seq, n_heads, head_dim), jnp.float32)
    k = jax.random.normal(ks[4], (batch, seq, n_kv_heads, head_dim),
                          jnp.float32)
    v = jax.random.normal(ks[5], (batch, seq, n_kv_heads, head_dim),
                          jnp.float32)
    return tuple(a.astype(dtype) for a in (x, scale, wo, q, k, v))


_KERNEL_PATTERNS = ("softmax_attention", "rmsnorm")


def _pattern_sites(ctx) -> dict:
    """pattern -> gene region for the phase's two kernel sites."""
    sites = {}
    for s in ctx.coding.sites:
        pat = ctx.graph.by_name(s.region).meta.get("pattern")
        if pat in _KERNEL_PATTERNS:
            require(pat not in sites, f"two gene sites match {pat}")
            sites[pat] = s.region
    require(set(sites) == set(_KERNEL_PATTERNS),
            f"kernel sites not found: matched {sites}")
    return sites


def _forced(ctx, sites: dict, dest: str) -> tuple:
    """The chromosome with every kernel site on ``dest`` and the rest on
    the reference path."""
    idx = ctx.coding.destinations.index(dest)
    chosen = set(sites.values())
    return tuple(idx if s.region in chosen else 0 for s in ctx.coding.sites)


def jaxpr_phase(*, batch: int = 2, seq: int = 2048, n_heads: int = 16,
                n_kv_heads: int = 8, head_dim: int = 128, d_model: int = 1024,
                seed: int = 0, population: int = 4, generations: int = 2,
                repeats: int = 3, tol: float = BF16_REL_L2,
                log: Callable[[str], None] = print) -> dict:
    """Plan :func:`attention_block` with the variant alphabet, then force
    both kernel sites onto Pallas and check what ran."""
    from repro.core import GAConfig, OffloadConfig, Offloader

    args = attention_block_args(batch=batch, seq=seq, n_heads=n_heads,
                                n_kv_heads=n_kv_heads, head_dim=head_dim,
                                d_model=d_model, seed=seed)
    log(f"jaxpr: attention_block q={args[3].shape} k/v={args[4].shape} "
        f"x={args[0].shape} dtype={args[0].dtype}")
    off = Offloader(OffloadConfig(
        frontend="jaxpr", repeats=repeats,
        ga=GAConfig(population=population, generations=generations,
                    seed=seed),
        options={"example_args": args}))
    with compile_clock() as clock:
        t0 = time.perf_counter()
        ctx = off.prepare(attention_block)
        res = off.search(ctx)
        plan_s = time.perf_counter() - t0
    sites = _pattern_sites(ctx)
    require(res.best.valid, f"no valid jaxpr plan: {res.best.detail}")
    log(f"jaxpr: alphabet={ctx.coding.destinations} sites={sites}")
    log(f"jaxpr: GA chose {res.report.substituted or 'reference'} "
        f"best_s={res.best.time_s:.6g} baseline_s={res.baseline.time_s:.6g} "
        f"plan_s={plan_s:.3f} compile_s={clock['seconds']:.3f} "
        f"({SMOKE_NOTE})")

    # the forced Pallas chromosome: nothing may fall back, and the kernels
    # must compile for the device
    art = off.apply(ctx, _forced(ctx, sites, "gpu_pallas"))
    want = {region: "pallas" for region in sites.values()}
    require(art.report.substituted == want and not art.report.fallbacks,
            f"requested Pallas kernels did not all run: substituted="
            f"{art.report.substituted} fallbacks={art.report.fallbacks}")
    try:
        compiled = jax.jit(art.fn).lower(*args).compile()
    except Exception as e:  # noqa: BLE001 — any compiler refusal fails here
        raise SmokeFailure(f"requested Pallas kernels failed to compile: "
                           f"{type(e).__name__}: {e}") from e
    custom_call = "tpu_custom_call" in compiled.as_text()
    if jax.default_backend() == "tpu":
        require(custom_call, "no tpu_custom_call in the compiled program")
    got = compiled(*args)
    with jax.default_matmul_precision("highest"):
        want_out = jax.jit(attention_block)(*_f32(args))
    err = rel_l2(got, want_out)
    max_err = float(err.max())
    require(bool(jnp.all(jnp.isfinite(got))), "non-finite kernel output")
    require(max_err <= tol, f"Pallas output off the float32 reference: max "
                            f"rel L2 {max_err:.4g} > {tol}")
    log(f"jaxpr: forced gpu_pallas substituted={art.report.substituted} "
        f"fallbacks={dict(art.report.fallbacks)} "
        f"tpu_custom_call={custom_call} max rel L2 vs float32 "
        f"{max_err:.4g} (tol {tol})")
    return {"sites": sites, "ga_substituted": dict(res.report.substituted),
            "forced_substituted": dict(art.report.substituted),
            "tpu_custom_call": custom_call, "max_rel_l2": max_err,
            "plan_s": plan_s, "compile_s": clock["seconds"]}


# ---------------------------------------------------------------------------
# (c) python_ast phase: a numeric loop program
# ---------------------------------------------------------------------------

RMS_LOOP_SRC = """
def rms_app(x, scale, n, d):
    out = np.zeros((n, d))
    for i in range(n):
        ss = 0.0
        for t in range(d):
            ss = ss + x[i][t] * x[i][t]
        inv = 1.0 / np.sqrt(ss / d + 1e-06)
        for t in range(d):
            out[i][t] = x[i][t] * inv * (1.0 + scale[t])
    return out
"""


def python_phase(*, rows: int = 64, cols: int = 128, seed: int = 0,
                 population: int = 4, generations: int = 2,
                 log: Callable[[str], None] = print) -> dict:
    """Plan :data:`RMS_LOOP_SRC`; its chosen variant must run and match a
    NumPy reference."""
    from repro.core import GAConfig, OffloadConfig, Offloader

    rng = np.random.default_rng(seed)
    inputs = {"x": rng.standard_normal((rows, cols)),
              "scale": rng.standard_normal(cols) * 0.1}
    t0 = time.perf_counter()
    res = Offloader(OffloadConfig(
        frontend="python_ast", repeats=1,
        ga=GAConfig(population=population, generations=generations,
                    seed=seed),
        options={"consts": {"n": rows, "d": cols}})).plan(RMS_LOOP_SRC,
                                                          inputs)
    plan_s = time.perf_counter() - t0
    chosen = {c.region: c.chosen for c in res.report.choices
              if c.pattern == "rmsnorm" and c.chosen != "ref"}
    require(bool(chosen), f"the rmsnorm loop stayed on the interpreter: "
                          f"{res.report.choices}")
    got = np.asarray(res.artifact.run(**inputs)["out"])
    x, sc = inputs["x"], inputs["scale"]
    want = x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + 1e-6) \
        * (1.0 + sc)
    max_err = float(rel_l2(got, want).max())
    require(max_err <= 1e-3, f"loop program output off NumPy: max rel L2 "
                             f"{max_err:.4g}")
    log(f"python_ast: rms_app n={rows} d={cols} chosen={chosen} "
        f"speedup_vs_interpreter={res.speedup:.4g} plan_s={plan_s:.3f} "
        f"({SMOKE_NOTE}) max rel L2 vs NumPy {max_err:.3g}")
    return {"chosen": chosen, "speedup": res.speedup, "plan_s": plan_s,
            "max_rel_l2": max_err}


# ---------------------------------------------------------------------------
# (d) mesh phase: mesh destinations and the sharded offload plan
# ---------------------------------------------------------------------------


def mesh_phase(moe_cfg, *, n: int = 4, batch: int = 4, seq: int = 2048,
               n_heads: int = 16, n_kv_heads: int = 8, head_dim: int = 128,
               d_model: int = 1024, loss_batch: int = 8,
               loss_seq: int = 256, seed: int = 0, population: int = 4,
               generations: int = 2, tol: float = BF16_REL_L2,
               loss_tol: float = 5e-3,
               log: Callable[[str], None] = print) -> dict:
    """On ``n`` devices: (1) plan :func:`attention_block` with mesh
    destinations and run both kernel sites on ``mesh:data:<n>``; (2) the
    offload plan's loss of ``moe_cfg`` sharded over a (data, model) mesh.
    Each against its single-device float32 reference."""
    from repro.core import GAConfig, OffloadConfig, Offloader
    from repro.launch.mesh import auto_mesh
    from repro.models import OFFLOAD_PLAN, REFERENCE_PLAN, build_model
    from repro.runtime import sharding as shd
    from repro.runtime.pspec import axis_rules

    require(jax.device_count() >= n,
            f"mesh phase needs {n} devices, found {jax.device_count()}")
    dest = f"mesh:data:{n}:batch"

    # (1) jaxpr plan with mesh destinations
    args = attention_block_args(batch=batch, seq=seq, n_heads=n_heads,
                                n_kv_heads=n_kv_heads, head_dim=head_dim,
                                d_model=d_model, seed=seed)
    off = Offloader(OffloadConfig(
        frontend="jaxpr", repeats=1,
        ga=GAConfig(population=population, generations=generations,
                    seed=seed),
        options={"example_args": args}))
    t0 = time.perf_counter()
    ctx = off.prepare(attention_block)
    require(dest in ctx.coding.destinations,
            f"{dest} not proposed: {ctx.coding.destinations}")
    res = off.search(ctx)
    plan_s = time.perf_counter() - t0
    require(res.best.valid, f"no valid mesh plan: {res.best.detail}")
    sites = _pattern_sites(ctx)
    art = off.apply(ctx, _forced(ctx, sites, dest))
    ran = {c.region: c.chosen for c in art.report.choices}
    require(all(ran.get(r) == dest for r in sites.values())
            and all("sharded over" in c.why for c in art.report.choices
                    if c.region in sites.values()),
            f"mesh destinations did not run sharded: "
            f"{art.report.choices}")
    got = jax.jit(art.fn)(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(attention_block)(*_f32(args))
    jerr = float(rel_l2(got, want).max())
    require(jerr <= tol, f"mesh plan output off the float32 reference: "
                         f"max rel L2 {jerr:.4g} > {tol}")
    log(f"mesh: jaxpr GA chose {dict(res.destinations)} plan_s={plan_s:.3f} "
        f"({SMOKE_NOTE}); forced {dest} on {list(sites.values())}: max rel "
        f"L2 vs float32 {jerr:.4g} (tol {tol})")

    # (2) the offload plan's loss, sharded over (data, model)
    model = build_model(moe_cfg)
    params = jax.jit(model.init)(jax.random.key(seed))
    batch_ = model.demo_batch(jax.random.key(seed + 1), loss_batch, loss_seq)
    ref_plan = REFERENCE_PLAN.replace(compute_dtype="float32")
    off_plan = OFFLOAD_PLAN.replace(compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        l_ref = float(jax.jit(lambda p, b: model.loss(p, b, ref_plan)[0])(
            params, batch_))
        model_axis = 2 if n % 2 == 0 else 1
        mesh = auto_mesh((n // model_axis, model_axis), ("data", "model"))
        rules = shd.make_rules(mesh)
        p_shard = shd.tree_shardings(
            rules, params, shd.param_logical_axes(model.param_shapes(),
                                                  moe_cfg, mesh))
        b_shard = shd.tree_shardings(rules, batch_,
                                     shd.batch_logical_axes(batch_))

        def loss_sharded(p, b):
            with axis_rules(rules):
                return model.loss(p, b, off_plan)[0]

        l_off = float(jax.jit(loss_sharded, in_shardings=(p_shard, b_shard))(
            jax.device_put(params, p_shard), jax.device_put(batch_, b_shard)))
    d = abs(l_ref - l_off)
    require(math.isfinite(l_off) and d <= loss_tol,
            f"sharded offload-plan loss {l_off:.6f} off the single-device "
            f"float32 reference {l_ref:.6f} by {d:.3g} > {loss_tol}")
    log(f"mesh: {moe_cfg.arch_id} loss on mesh {dict(mesh.shape)} "
        f"offload plan={l_off:.6f} single-device float32 reference="
        f"{l_ref:.6f} |d|={d:.3g} (tol {loss_tol})")
    return {"jaxpr_max_rel_l2": jerr, "ga_destinations":
            dict(res.destinations), "loss_ref": l_ref, "loss_sharded": l_off,
            "loss_abs_diff": d, "mesh": dict(mesh.shape)}


def moe_mesh_config(cfg, n_layers: int = 2):
    """``cfg`` cut to ``n_layers`` layers, with a dropless expert capacity
    (capacity factor E/k) so the scatter dispatch computes exactly what the
    dense reference does."""
    moe = dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.n_experts) / cfg.moe.top_k)
    return dataclasses.replace(cfg, n_layers=n_layers, moe=moe)
