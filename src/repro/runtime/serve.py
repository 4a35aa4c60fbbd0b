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
"""
from __future__ import annotations

import collections
import functools
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
        self.decode = jax.jit(
            lambda p, tok, st: model.decode(p, tok, st, plan),
            donate_argnums=(2,))
        self._prefill: dict = {}

    def prefill_fn(self, cache_capacity: int):
        if cache_capacity not in self._prefill:
            model, plan = self._model, self.plan
            self._prefill[cache_capacity] = jax.jit(
                functools.partial(
                    lambda p, inp: model.prefill(
                        p, inp, plan, cache_capacity=cache_capacity)))
        return self._prefill[cache_capacity]


class Server:
    def __init__(self, model: Model, params, plan: ExecPlan,
                 cfg: Optional[ServeConfig] = None):
        self.model = model
        self.params = params
        self.cfg = cfg or ServeConfig()
        self._bound = _Bound(model, plan)
        # request-arrival timestamps for traffic_hz(): the signal the
        # planning service's operating-point policy reads (latency-optimal
        # under load, energy-optimal idle)
        self._req_times: collections.deque = collections.deque(maxlen=256)

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
        self._req_times.append(t0)
        obs_metrics.gauge("serve.traffic_hz").set(self.traffic_hz())
        with obs_trace.span("serve.generate", batch=b, prompt_len=s,
                            max_new=max_new):
            cap = s + max_new + (self.model.cfg.vision_patches or 0)
            logits, state = bound.prefill_fn(cap)(self.params, inputs)
            key = jax.random.key(self.cfg.seed)
            out = np.zeros((b, max_new), np.int32)
            seen = [logits[:, -1]] if return_logits else None
            tok = self._sample(logits, key, 0)
            for i in range(max_new):
                out[:, i] = np.asarray(tok[:, 0])
                if i == max_new - 1:
                    break
                logits, state = bound.decode(self.params, tok, state)
                if return_logits:
                    seen.append(logits[:, -1])
                tok = self._sample(logits, key, i + 1)
        # the histogram lives in the process-wide registry keyed by name,
        # not on the _Bound snapshot — a mid-flight swap_plan publishes a
        # new snapshot but cannot reset the latency series
        obs_metrics.histogram("serve.generate_seconds").observe(
            time.perf_counter() - t0)
        if return_logits:
            return out, jnp.stack(seen, axis=1)
        return out

    def traffic_hz(self, window_s: float = 60.0) -> float:
        """Recent request rate (requests/s over the trailing window) — feed
        it to :meth:`repro.service.service.PlanService.select_for_traffic`
        to pick the right Pareto operating point for the current load."""
        if window_s <= 0:
            return 0.0
        cutoff = time.perf_counter() - float(window_s)
        return sum(1 for t in self._req_times if t >= cutoff) / float(window_s)

    def _sample(self, logits, key, i):
        lg = logits[:, -1].astype(jnp.float32)
        if self.cfg.temperature <= 0:
            return jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
        k = jax.random.fold_in(key, i)
        return jax.random.categorical(
            k, lg / self.cfg.temperature, axis=-1)[:, None].astype(jnp.int32)
