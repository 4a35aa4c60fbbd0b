"""Serving loop: batched prefill + autoregressive decode with KV caches.

``Server`` owns params + plan; ``generate`` pads a request batch to the
static shapes, prefills, then decodes greedily or with temperature sampling.
The decode loop donates the state so caches update in place.

The plan is **hot-swappable**: everything derived from it (the jitted decode
fn, the per-capacity prefill cache) lives in one immutable ``_Bound``
snapshot published by a single reference assignment.  ``generate`` reads the
snapshot once per call, so an in-flight generation always runs one complete
plan end-to-end — a concurrent :meth:`Server.swap_plan` (the planning
service's hot-swap) takes effect on the *next* call, never mid-sequence.
``Server.from_store`` constructs a server straight from a persisted plan
fingerprint, with no planner in the loop.

The jitted steps are named functions, so their device programs read
``jit_serve_prefill`` and ``jit_serve_decode`` in a profiler trace.  Each
``generate`` opens the span tree ``serve.generate`` > ``serve.prefill``,
then per token ``serve.sample`` (its dispatches), ``serve.token_to_host``
(the copy that waits for the token) and ``serve.decode_step`` (the decode
dispatch); ``serve.generate`` carries, and the ``serve.kv_cache_bytes`` gauge
holds, the bytes of the decode state the call allocated.  With the profiler
sink on (``repro.obs.enable_profiler``) the spans land on the profiler's
clock beside the device's operations.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.models.plan import ExecPlan
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


@dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 = greedy
    seed: int = 0


class _Bound:
    """One plan plus everything jitted against it.  Immutable after
    construction (the prefill dict only memoizes pure jit wrappers per
    capacity — idempotent, so racing fills are harmless)."""

    __slots__ = ("plan", "decode", "_model", "_prefill")

    def __init__(self, model: Model, plan: ExecPlan):
        self.plan = plan
        self._model = model

        def serve_decode(p, tok, st):
            return model.decode(p, tok, st, plan)

        self.decode = jax.jit(serve_decode, donate_argnums=(2,))
        self._prefill: dict = {}

    def prefill_fn(self, cache_capacity: int):
        if cache_capacity not in self._prefill:
            model, plan = self._model, self.plan

            def serve_prefill(p, inp):
                return model.prefill(p, inp, plan,
                                     cache_capacity=cache_capacity)

            self._prefill[cache_capacity] = jax.jit(serve_prefill)
        return self._prefill[cache_capacity]


class Server:
    def __init__(self, model: Model, params, plan: ExecPlan,
                 cfg: Optional[ServeConfig] = None):
        self.model = model
        self.params = params
        self.cfg = cfg or ServeConfig()
        self._bound = _Bound(model, plan)

    @classmethod
    def from_store(cls, model: Model, params, store, fingerprint: str,
                   cfg: Optional[ServeConfig] = None) -> "Server":
        """Construct a server from a persisted plan: loads the newest
        :class:`~repro.service.store.PlanRecord` for ``fingerprint`` from a
        :class:`~repro.service.store.PlanStore` and rehydrates its
        ``ExecPlan`` — no search, no planner in the loop."""
        rec = store.load(fingerprint)
        if rec is None:
            raise LookupError(
                f"no stored plan for fingerprint {fingerprint!r} — run the "
                f"planning service (or Offloader.plan) first")
        plan = store.rehydrate(rec)
        if not isinstance(plan, ExecPlan):
            raise TypeError(
                f"stored plan for {fingerprint!r} rehydrates to "
                f"{type(plan).__name__}, not an ExecPlan — Server only "
                f"serves module-frontend plans")
        return cls(model, params, plan, cfg)

    @property
    def plan(self) -> ExecPlan:
        return self._bound.plan

    def swap_plan(self, plan: ExecPlan) -> None:
        """Hot-swap the execution plan.  Builds the new plan's jitted
        closures first, then publishes them in one reference assignment:
        concurrent ``generate`` calls finish on the plan they started with
        and the next call picks this one up — never a torn mix."""
        self._bound = _Bound(self.model, plan)

    def generate(self, inputs: dict, max_new: Optional[int] = None,
                 return_logits: bool = False):
        """inputs: dict with 'tokens' (B,S) (+ frames/patch_feats).  Returns
        generated tokens (B, max_new); with ``return_logits`` also the
        logits each token was sampled from, (B, max_new, V) on the device."""
        bound = self._bound          # one snapshot: the whole call runs one
        max_new = max_new or self.cfg.max_new_tokens   # complete plan
        tokens = inputs["tokens"]
        b, s = tokens.shape
        t0 = time.perf_counter()
        span = obs_trace.span
        with span("serve.generate", batch=b, prompt_len=s,
                  max_new=max_new) as gen:
            cap = s + max_new + (self.model.cfg.vision_patches or 0)
            with span("serve.prefill"):
                logits, state = bound.prefill_fn(cap)(self.params, inputs)
            state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
            obs_metrics.gauge("serve.kv_cache_bytes").set(state_bytes)
            gen.set(kv_cache_bytes=state_bytes)
            key = jax.random.key(self.cfg.seed)
            out = np.zeros((b, max_new), np.int32)
            seen = [logits[:, -1]] if return_logits else None
            with span("serve.sample"):
                tok = self._sample(logits, key, 0)
            for i in range(max_new):
                with span("serve.token_to_host"):
                    out[:, i] = np.asarray(tok[:, 0])
                if i == max_new - 1:
                    break
                with span("serve.decode_step"):
                    logits, state = bound.decode(self.params, tok, state)
                if return_logits:
                    seen.append(logits[:, -1])
                with span("serve.sample"):
                    tok = self._sample(logits, key, i + 1)
        # the histogram lives in the process-wide registry keyed by name,
        # not on the _Bound snapshot — a mid-flight swap_plan publishes a
        # new snapshot but cannot reset the latency series
        obs_metrics.histogram("serve.generate_seconds").observe(
            time.perf_counter() - t0)
        if return_logits:
            return out, jnp.stack(seen, axis=1)
        return out

    def step_hlo(self, batch: int, prompt_len: int,
                 max_new: Optional[int] = None) -> dict:
        """The optimized HLO text of the two programs ``generate`` runs for
        a tokens-only request of ``batch`` x ``prompt_len``, by module name
        (``jit_serve_prefill``, ``jit_serve_decode``).  Each instruction's
        ``op_name`` metadata names its model region (``embed``,
        ``attention``, ``kv_cache``, ``mlp``, ``moe``, ``norm``, ``head``):
        the map from a trace's operation names to regions.  Compiles both
        programs."""
        bound = self._bound
        max_new = max_new or self.cfg.max_new_tokens
        cap = prompt_len + max_new + (self.model.cfg.vision_patches or 0)
        prefill = bound.prefill_fn(cap)
        inputs = {"tokens": jax.ShapeDtypeStruct((batch, prompt_len),
                                                 jnp.int32)}
        _, state = jax.eval_shape(prefill, self.params, inputs)
        token = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        return {
            "jit_serve_prefill":
                prefill.lower(self.params, inputs).compile().as_text(),
            "jit_serve_decode": bound.decode.lower(
                self.params, token, state).compile().as_text(),
        }

    def _sample(self, logits, key, i):
        lg = logits[:, -1].astype(jnp.float32)
        if self.cfg.temperature <= 0:
            return jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
        k = jax.random.fold_in(key, i)
        return jax.random.categorical(
            k, lg / self.cfg.temperature, axis=-1)[:, None].astype(jnp.int32)
