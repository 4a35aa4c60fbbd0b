"""Serving driver: one closed-loop caller sends requests to the program's
``Server`` back to back, each a batch of prompts of one length bucket.

Traffic keys (``traffic/<mix>.json`` with ``"driver": "serve"``):

* ``batch``, ``prompt_lengths`` (the buckets), ``new_tokens`` (greedy);
* ``planner``: ``population``, ``generations``, ``seed`` of the module
  frontend's GA that picks the served plan in set-up;
* ``check_requests``, ``check_rows``: how many finished requests the
  check samples (the longest bucket always among them), and how many rows
  the reference runs at once;
* ``trace_requests``: requests at the start of the window that a traced
  run records.

Set-up makes the weights from the seed, plans, stores the plan, builds the
server from the store and sends one request per bucket.  Buckets follow
each other in pairs, one of each in an order drawn from the seed, so every
seed asks for the same work.  After the window a sample of finished
requests is checked against the float32 reference, teacher-forced on the
served tokens: per served token, the gap by which its logit lies below the
reference's best, read as the widest or the mean gap (``READINGS``).
"""
from __future__ import annotations

import importlib
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, reference, weights
from chipbench.harness import Context, Run, memory_peak_bytes


def arch_config(c: dict):
    """The program's ``ArchConfig`` for configuration file ``c``."""
    from repro.configs.base import ArchConfig, MoEConfig

    moe = None
    if c.get("num_experts"):
        moe = MoEConfig(n_experts=c["num_experts"],
                        top_k=c["num_experts_per_tok"],
                        d_ff_expert=c["intermediate_size"],
                        capacity_factor=float(
                            c["assumed"]["capacity_factor"]))
    return ArchConfig(
        arch_id=c["program_arch"], family="moe" if moe else "dense",
        source=c["source"], n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or 0, d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], attn_kind="full",
        qk_norm=c.get("qk_norm") == "per_head", mlp_act="silu",
        moe=moe, rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        norm_eps=float(c["rms_norm_eps"]))


def schedule(seed: int, buckets: list, n: int) -> list:
    """Prompt length of requests 0..n-1: the buckets in rounds, each
    round in an order drawn from the seed."""
    rng = np.random.default_rng([seed, 2])
    rounds = -(-n // len(buckets))
    order = np.argsort(rng.random((rounds, len(buckets))), axis=1)
    return [buckets[i] for i in order.ravel()[:n]]


def prompt(seed: int, i: int, batch: int, length: int, vocab: int):
    """Token ids of request ``i`` (``i < 0``: warm-up requests)."""
    rng = np.random.default_rng([seed, 3, i + 1_000_000])
    return rng.integers(0, vocab, (batch, length), dtype=np.int32)


def build_server(ctx: Context, params, tmp: str):
    """Plan with the module frontend, store the plan, serve it from the
    store."""
    from repro import roofline as rl
    from repro.core import GAConfig, OffloadConfig, Offloader
    from repro.models import build_model
    from repro.runtime.serve import ServeConfig, Server
    from repro.service import PlanStore, record_from_result

    c, t = ctx.config, ctx.traffic
    cfg = arch_config(c)
    model = build_model(cfg)
    want = model.param_shapes(jnp.bfloat16)
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            w.shape != g.shape for w, g in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(params))):
        raise ValueError("the benchmark's weight layout no longer matches "
                         "the program's parameter tree")
    batch, longest = t["batch"], max(t["prompt_lengths"])
    cap = longest + t["new_tokens"]
    param_specs = jax.eval_shape(lambda: params)
    token_specs = {"tokens": jax.ShapeDtypeStruct((batch, longest),
                                                  jnp.int32)}

    def lower_fn(plan):
        return jax.jit(lambda p, inp: model.prefill(
            p, inp, plan, cache_capacity=cap)).lower(param_specs, token_specs)

    p = t["planner"]
    off = Offloader(OffloadConfig(
        frontend="module",
        ga=GAConfig(population=p["population"],
                    generations=p["generations"], seed=p["seed"]),
        options={"lower_fn": lower_fn, "n_devices": 1,
                 "device_kind": ctx.device_kind,
                 "model_flops": rl.model_flops_infer(
                     cfg.param_count(active_only=True), batch * longest)}))
    plan_ctx = off.prepare(cfg)
    res = off.search(plan_ctx)
    if not res.best.valid:
        raise RuntimeError(f"no valid plan: {res.best.detail}")
    store = PlanStore(tempfile.mkdtemp(prefix="plans_", dir=tmp))
    store.put(record_from_result(res, plan_ctx.fingerprint,
                                 meta={"benchmark": ctx.cell}))
    server = Server.from_store(model, params, store, plan_ctx.fingerprint,
                               ServeConfig(max_new_tokens=t["new_tokens"]))
    ctx.log(f"plan: {dict(res.pattern)} -> serving "
            f"{ {f: getattr(server.plan, f) for f, _, _ in server.plan.OFFLOAD_SITES} }")
    return server


def run(ctx: Context, tmp: str) -> Run:
    c, t = ctx.config, ctx.traffic
    out = Run(config=c, traffic=t, device_kind=ctx.device_kind)
    batch, new, buckets = t["batch"], t["new_tokens"], t["prompt_lengths"]
    vocab = c["vocab_size"]

    with common.compile_clock() as setup_compiles:
        params = weights.make(c, ctx.seed)
        server = build_server(ctx, params, tmp)
        for j, length in enumerate(buckets):
            server.generate({"tokens": prompt(ctx.seed, -1 - j, batch, length,
                                              vocab)})
    profile = common.Profile(tmp) if ctx.trace else None
    lengths = schedule(ctx.seed, buckets, 1 << 14)
    out.setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"set-up {out.setup_s:.3f} s, {setup_compiles['count']} compiles "
            f"({setup_compiles['seconds']:.3f} s)")

    served, client_gaps = [], []
    deadline = time.perf_counter() + ctx.seconds
    with common.compile_clock() as window_compiles:
        if profile:
            profile.start()
        t_prev = None
        i = 0
        while time.perf_counter() < deadline:
            tokens = prompt(ctx.seed, i, batch, lengths[i], vocab)
            out.attempted += 1
            t0 = time.perf_counter()
            if t_prev is not None:
                client_gaps.append(t0 - t_prev)
            try:
                with common.annotate("request", profile is not None):
                    got = server.generate({"tokens": tokens})
            except Exception as e:  # noqa: BLE001 — a failed request counts
                out.failed += 1
                ctx.log(f"request {i} failed: {type(e).__name__}: {e}")
                got = None
            t1 = t_prev = time.perf_counter()
            if got is not None:
                out.requests.append({"t0": t0, "t1": t1, "prompt": lengths[i],
                                     "batch": batch, "new": new})
                served.append((i, got))
            i += 1
            if profile and profile.active and i >= t["trace_requests"]:
                profile.stop()
        if profile:
            profile.stop()
    if out.requests:
        out.window = (out.requests[0]["t0"], out.requests[-1]["t1"])
    out.compiles_in_window = window_compiles["count"]
    out.memory_peak_bytes = memory_peak_bytes()
    lat = [r["t1"] - r["t0"] for r in out.requests]
    ctx.log(f"window: {out.attempted} requests attempted, "
            f"{len(out.requests)} completed, {out.failed} failed; "
            f"{out.compiles_in_window} compiles inside the window "
            f"({window_compiles['seconds']:.3f} s)")
    if lat:
        ctx.log(f"latency s: median {statistics.median(lat):.4f} "
                f"max {max(lat):.4f}; client gap between requests s: "
                f"median {statistics.median(client_gaps or [0]):.6f} "
                f"max {max(client_gaps or [0]):.6f} (closed loop: how late "
                f"the caller sent)")
    ctx.log(f"peak_bytes_in_use {out.memory_peak_bytes}")
    del server
    if profile:
        out.trace = profile.reduce()

    out.checks, out.control = check(ctx, params, served, lengths)
    return out


def sample(seed: int, served: list, lengths: list, k: int) -> list:
    """``k`` finished requests drawn from the seed, the longest bucket
    always among them."""
    if not served:
        return []
    rng = np.random.default_rng([seed, 4])
    longest = max(lengths[i] for i, _ in served)
    top = [j for j, (i, _) in enumerate(served) if lengths[i] == longest]
    first = top[int(rng.integers(len(top)))]
    rest = [j for j in range(len(served)) if j != first]
    pick = [first] + list(rng.permutation(rest)[:max(0, k - 1)])
    return [served[j] for j in sorted(pick)]


def reference_of(c: dict):
    """The plain reference configuration ``c`` names (``"reference":
    "<module>.<function>"`` under ``chipbench``)."""
    mod, fn = c["reference"].rsplit(".", 1)
    return getattr(importlib.import_module(f"chipbench.{mod}"), fn)


def gaps(ctx: Context, params, picked: list, lengths: list,
         low: bool = False) -> np.ndarray:
    """Per served token of ``picked``: the reference's best logit less the
    logit of the token.  ``low``: the token the fp8 control puts first
    instead of the served one (the control's reading)."""
    c, t = ctx.config, ctx.traffic
    rows, out, logits = t["check_rows"], [], reference_of(c)
    for i, toks in picked:
        toks = np.asarray(toks)
        seq = np.concatenate(
            [prompt(ctx.seed, i, t["batch"], lengths[i], c["vocab_size"]),
             toks[:, :-1]], axis=1)
        for r in range(0, seq.shape[0], rows):
            ref = logits(params, c, seq[r:r + rows], toks.shape[1])
            chosen = toks[r:r + rows]
            if low:
                chosen = np.asarray(jnp.argmax(logits(
                    params, c, seq[r:r + rows], toks.shape[1], low=True), -1))
            out.append(reference.widest_gap(ref, chosen).ravel())
    return np.concatenate(out) if out else np.zeros(0)


#: what a check may compare, read from the per-token gaps of the sample;
#: ``limits/<cell>.json`` names the ones a cell compares
READINGS = {
    "widest_logit_gap": lambda g: float(g.max()),
    "mean_logit_gap": lambda g: float(g.mean()),
}


def _readings(g: np.ndarray) -> dict:
    return {k: f(g) if g.size else float("inf") for k, f in READINGS.items()}


def check(ctx: Context, params, served: list, lengths: list) -> tuple:
    """The checks ({name: (value, limit)}) and, where the context asks,
    the control's readings of the same sample ({name: value})."""
    picked = sample(ctx.seed, served, lengths, ctx.traffic["check_requests"])
    g = gaps(ctx, params, picked, lengths)
    got = _readings(g)
    ctx.log(f"check: {len(picked)} requests, {g.size} served tokens vs the "
            f"float32 reference: {got}; share of served tokens the "
            f"reference puts below its best {float((g > 0).mean()) if g.size else float('nan')!r}")
    control = {}
    if ctx.control:
        gc = gaps(ctx, params, picked, lengths, low=True)
        low = _readings(gc)
        control = {k: low[k] for k in ctx.limits}
        ctx.log(f"control (fp8 reference): {low}; share below the best "
                f"{float((gc > 0).mean())!r}")
    return {k: (got[k], lim) for k, lim in ctx.limits.items()}, control
