"""Serving driver for a DeepSeek-V2 configuration (latent attention, a
leading dense layer, a held share of the routed experts): the request loop
of ``drivers/serve.py`` over the same traffic keys, with the configuration's
own ``ArchConfig`` and weight layout.  The schedule, prompts, the checked
sample and the check (teacher-forced logit gaps against
``reference_mla.decoder_logits``) are ``serve.py``'s.

A traced run also reads the profile's operations by model region
(``spans.load``) and records, in ``Run.spans``, the device self time of the
``attention`` region (``region.attention``) and the device busy time inside
the traced requests (``requests.busy``), for the per-layer readers.
"""
from __future__ import annotations

import statistics
import tempfile
import time

import jax
import jax.numpy as jnp

from chipbench import common, spans, weights
from chipbench.drivers.serve import check, prompt, schedule
from chipbench.harness import Context, Run, memory_peak_bytes


def arch_config(c: dict):
    """The program's ``ArchConfig`` for configuration file ``c``.  The
    program has no routed scale and one YaRN mscale: a configuration that
    needs either is refused here (the reference keeps both formulas)."""
    from repro.configs.base import ArchConfig, MoEConfig, YarnRope

    y = c["rope_scaling"]
    if c["routed_scaling_factor"] != 1 or y["mscale"] != y["mscale_all_dim"]:
        raise ValueError("the program serves routed_scaling_factor 1 and "
                         "mscale == mscale_all_dim only")
    moe = MoEConfig(
        n_experts=c["router_experts"], top_k=c["num_experts_per_tok"],
        d_ff_expert=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        capacity_factor=float(c["assumed"]["capacity_factor"]),
        norm_topk=bool(c["norm_topk_prob"]),
        held_first=c["held_first"], held_count=c["n_routed_experts"])
    return ArchConfig(
        arch_id=c["program_arch"], family="moe", source=c["source"],
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], attn_kind="mla",
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        mlp_act="silu", moe=moe, n_dense_layers=c["first_k_dense_replace"],
        rope_theta=float(c["rope_theta"]),
        rope_yarn=YarnRope(
            factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"])),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        norm_eps=float(c["rms_norm_eps"]))


def shapes(c: dict) -> dict:
    """name path -> (shape, dtype name) of every weight: the leading dense
    layers under ``dense_blocks``, the MoE layers under ``blocks``."""
    d, v, nh = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    r, dr = c["kv_lora_rank"], c["qk_rope_head_dim"]
    qk, kv = c["qk_nope_head_dim"] + dr, c["qk_nope_head_dim"] + \
        c["v_head_dim"]
    nd = c["first_k_dense_replace"]
    nm = c["num_hidden_layers"] - nd
    ff, fe, e = c["intermediate_size"], c["moe_intermediate_size"], \
        c["n_routed_experts"]
    fs = c["n_shared_experts"] * fe
    out = {("embed",): ((v, d), "bf16"), ("lm_head",): ((v, d), "bf16"),
           ("final_norm",): ((d,), "bf16")}
    for group, n in (("dense_blocks", nd), ("blocks", nm)):
        b = (group,)
        out[b + ("ln1",)] = ((n, d), "bf16")
        out[b + ("ln2",)] = ((n, d), "bf16")
        out[b + ("attn", "wq")] = ((n, d, nh * qk), "bf16")
        out[b + ("attn", "wkv_a")] = ((n, d, r + dr), "bf16")
        out[b + ("attn", "kv_norm")] = ((n, r), "bf16")
        out[b + ("attn", "wkv_b")] = ((n, r, nh * kv), "bf16")
        out[b + ("attn", "wo")] = ((n, nh * c["v_head_dim"], d), "bf16")
    b = ("dense_blocks", "mlp")
    out[b + ("w_gate",)] = ((nd, d, ff), "bf16")
    out[b + ("w_up",)] = ((nd, d, ff), "bf16")
    out[b + ("w_down",)] = ((nd, ff, d), "bf16")
    b = ("blocks", "moe")
    out[b + ("w_router",)] = ((nm, d, c["router_experts"]), "f32")
    out[b + ("w_gate",)] = ((nm, e, d, fe), "bf16")
    out[b + ("w_up",)] = ((nm, e, d, fe), "bf16")
    out[b + ("w_down",)] = ((nm, e, fe, d), "bf16")
    out[b + ("shared", "w_gate")] = ((nm, d, fs), "bf16")
    out[b + ("shared", "w_up")] = ((nm, d, fs), "bf16")
    out[b + ("shared", "w_down")] = ((nm, fs, d), "bf16")
    return out


def make_weights(c: dict, seed: int) -> dict:
    """The weight tree of configuration ``c`` for ``seed``."""
    spec = tuple((path, shape, dt) for path, (shape, dt) in
                 sorted(shapes(c).items()))
    leaves = weights._make(weights.key_from_seed(seed, 1), spec)
    tree: dict = {}
    for (path, _, _), leaf in zip(spec, leaves):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree


def build_server(ctx: Context, cfg, params, tmp: str):
    """Plan with the module frontend, store the plan, serve it from the
    store (as ``serve.build_server``, for this ``ArchConfig``)."""
    from repro import roofline as rl
    from repro.core import GAConfig, OffloadConfig, Offloader
    from repro.models import build_model
    from repro.runtime.serve import ServeConfig, Server
    from repro.service import PlanStore, record_from_result

    t = ctx.traffic
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
    cfg = arch_config(c)          # first: a program without MLA stops here
    out = Run(config=c, traffic=t, device_kind=ctx.device_kind)
    batch, new, buckets = t["batch"], t["new_tokens"], t["prompt_lengths"]
    vocab = c["vocab_size"]

    with common.compile_clock() as setup_compiles:
        params = make_weights(c, ctx.seed)
        server = build_server(ctx, cfg, params, tmp)
        for j, length in enumerate(buckets):
            server.generate({"tokens": prompt(ctx.seed, -1 - j, batch, length,
                                              vocab)})
    profile = common.Profile(tmp) if ctx.trace else None
    lengths = schedule(ctx.seed, buckets, 1 << 14)
    out.setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"set-up {out.setup_s:.3f} s, {setup_compiles['count']} compiles "
            f"({setup_compiles['seconds']:.3f} s)")

    served = []
    deadline = time.perf_counter() + ctx.seconds
    with common.compile_clock() as window_compiles:
        if profile:
            profile.start()
        i = 0
        while time.perf_counter() < deadline:
            tokens = prompt(ctx.seed, i, batch, lengths[i], vocab)
            out.attempted += 1
            t0 = time.perf_counter()
            try:
                with common.annotate("request", profile is not None):
                    got = server.generate({"tokens": tokens})
            except Exception as e:  # noqa: BLE001 — a failed request counts
                out.failed += 1
                ctx.log(f"request {i} failed: {type(e).__name__}: {e}")
                got = None
            t1 = time.perf_counter()
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
                f"max {max(lat):.4f}; by prompt: "
                f"{ {n: [round(r['t1'] - r['t0'], 4) for r in out.requests if r['prompt'] == n] for n in buckets} }")
    ctx.log(f"peak_bytes_in_use {out.memory_peak_bytes}")
    del server
    if profile:
        regions = spans.load(profile.dir)
        out.trace = profile.reduce()
        out.spans = [
            {"name": "region.attention",
             "dur_s": regions.region_self_s("attention")},
            {"name": "requests.busy",
             "dur_s": sum(out.trace.busy_s(s, e)
                          for s, e in out.trace.spans("request"))}]
        ctx.log(f"traced: {out.spans}; device self s by region "
                f"{ {r: round(regions.region_self_s(r), 6) for r in spans.REGIONS + (spans.UNSCOPED,)} }")

    out.checks, out.control = check(ctx, params, served, lengths)
    return out
