"""Fitness evaluators: the "verification environment" measurements.

Two measurement backends, both measuring real artifacts (the paper's
anti-static-prediction stance, §3.1):

* :class:`WallClockFitness` — execute and time (min over repeats after a
  warm-up compile), verify results against the reference path (PCAST
  analogue) -> invalid = time ∞.
* :class:`CostModelFitness` — AOT ``lower().compile()`` at production scale
  on the production mesh; the measured artifact is the compiled binary:
  roofline step time (against the target chip's published peaks) as the
  objective, per-device HBM fit as the validity check (OOM -> time ∞, like
  a compile error in the paper).

Both are plain ``bits -> Evaluation`` callables; caching, dedup, parallel
dispatch and persistence belong to :mod:`repro.core.evaluator`, not here.
``CostModelFitness`` holds no mutable state across calls and is safe to
invoke from evaluator worker threads/processes; ``WallClockFitness`` timings
only mean something when measured one at a time (keep ``workers=0``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.core.ga import Evaluation
from repro.core.verifier import verify
from repro import roofline as rl


# ---------------------------------------------------------------------------
# wall-clock fitness (smoke scale, real execution)
# ---------------------------------------------------------------------------


@dataclass
class PreparedRun:
    """Output of :meth:`WallClockFitness.prepare`: a compiled, verified
    runner awaiting its (strictly serial) timing loop — or the failure
    Evaluation that takes its place."""

    bits: tuple
    runner: Optional[Callable[[], Any]] = None
    failure: Optional[Evaluation] = None   # build/compile/verify outcome


@dataclass
class WallClockFitness:
    """bits -> build(bits) -> callable; timed and verified vs reference.

    Two-phase: :meth:`prepare` does everything that need not be serial —
    build the artifact, run the warm-up (compilation; releases the GIL
    inside XLA), verify against the reference — and :meth:`measure` runs
    the timing loop, which only means something measured one at a time.
    ``__call__`` chains them (the historical serial behavior); the
    evaluation engine overlaps different chromosomes' ``prepare`` calls
    ahead of a serial ``measure`` pass (``Evaluator.compile_workers``).
    ``build`` must therefore be safe to invoke from concurrent threads
    (every shipped builder constructs a fresh runner per call).
    """

    build: Callable[[tuple], Callable[[], Any]]   # returns a nullary runner
    reference_output: Any = None                  # captured from all-off if None
    repeats: int = 3
    rtol: float = 1e-2
    atol: float = 1e-2
    verify_outputs: bool = True

    def prepare(self, bits: tuple) -> PreparedRun:
        bits = tuple(bits)
        try:
            runner = self.build(bits)
            out = runner()                        # warm-up (compilation)
            out = jax.tree_util.tree_map(
                lambda x: np.asarray(x) if hasattr(x, "dtype") else x, out)
        except Exception as e:  # noqa: BLE001 — paper: errors leave the GA
            return PreparedRun(bits, failure=Evaluation(
                bits, float("inf"), False,
                {"error": f"{type(e).__name__}: {e}"[:300]}))
        if self.verify_outputs and self.reference_output is not None:
            v = verify(self.reference_output, out, self.rtol, self.atol)
            if not v.ok:
                return PreparedRun(bits, failure=Evaluation(
                    bits, float("inf"), False,
                    {"verify": f"max_abs={v.max_abs:.3g} "
                               f"max_rel={v.max_rel:.3g} {v.detail}"}))
        return PreparedRun(bits, runner=runner)

    def measure(self, prepared: PreparedRun) -> Evaluation:
        if prepared.failure is not None:
            return prepared.failure
        best = float("inf")
        for _ in range(self.repeats):
            t0 = time.perf_counter()
            out2 = prepared.runner()
            jax.tree_util.tree_map(
                lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
                out2)
            best = min(best, time.perf_counter() - t0)
        return Evaluation(prepared.bits, best, True, {})

    def __call__(self, bits: tuple) -> Evaluation:
        return self.measure(self.prepare(bits))


# ---------------------------------------------------------------------------
# cost-model fitness (production scale, AOT compile + roofline)
# ---------------------------------------------------------------------------


@dataclass
class CostModelFitness:
    """bits -> lower/compile -> roofline step time; OOM/compile error = ∞.

    ``lower`` maps bits to a jax.stages.Lowered (the caller owns mesh,
    shardings and input specs).  ``device_kind`` names the chip the program
    is compiled for, whose peaks (:func:`repro.roofline.peaks_for`) score
    it; ``None`` means this process's first device.  ``hbm_budget`` is
    per-device bytes, by default that chip's memory.
    """

    lower: Callable[[tuple], Any]
    n_devices: int
    model_flops: float = 0.0
    device_kind: Optional[str] = None
    hbm_budget: Optional[float] = None

    def __post_init__(self):
        if self.device_kind is None:
            self.device_kind = jax.devices()[0].device_kind
        peaks = rl.peaks_for(self.device_kind)     # unknown kind: raise now
        if self.hbm_budget is None:
            self.hbm_budget = peaks.hbm_bytes

    def __call__(self, bits: tuple) -> Evaluation:
        try:
            lowered = self.lower(bits)
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001
            return Evaluation(bits, float("inf"), False,
                              {"error": f"{type(e).__name__}: {e}"[:300]})
        try:
            mem = compiled.memory_analysis()
            live = (getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "output_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)
                    + getattr(mem, "generated_code_size_in_bytes", 0))
        except Exception:  # pragma: no cover — backend without memory stats
            mem, live = None, 0
        roof = rl.analyze(compiled, compiled.as_text(), self.n_devices,
                          model_flops_global=self.model_flops,
                          device_kind=self.device_kind)
        detail = {"roofline": roof.summary(), "live_bytes": live}
        if live > self.hbm_budget:
            return Evaluation(bits, float("inf"), False,
                              {**detail, "error": f"OOM: {live/1e9:.2f} GB "
                                                  f"> {self.hbm_budget/1e9:.0f} GB"})
        return Evaluation(bits, roof.step_s, True, detail)
