"""Observability layer: tracing spans, metrics registry, logging setup.

Zero-dependency (stdlib + ``repro.journal`` only) so every layer —
core, frontends, kernels, service, runtime, launch — can import it
without cycles; the profiler sink imports ``jax.profiler`` when enabled.  See docs/api.md ("Observability") for naming
conventions and the obsreport CLI.
"""
from repro.obs import metrics
from repro.obs.log import get_logger, setup as setup_logging
from repro.obs.metrics import (REGISTRY, counter, gauge, histogram,
                               render_prometheus, snapshot)
from repro.obs.trace import (NULL_SPAN, Tracer, active_tracer,
                             current_span_id, disable, disable_profiler,
                             enable, enable_profiler, maybe_tracing,
                             read_trace, span)

__all__ = [
    "metrics", "REGISTRY", "counter", "gauge", "histogram",
    "snapshot", "render_prometheus",
    "span", "current_span_id", "maybe_tracing", "enable", "disable",
    "enable_profiler", "disable_profiler", "active_tracer", "Tracer",
    "NULL_SPAN", "read_trace",
    "get_logger", "setup_logging",
]
