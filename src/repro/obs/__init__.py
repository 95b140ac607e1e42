"""Observability: the telemetry spine (spans on the profiler's clock,
counters, query traces), Perfetto export, and the model-vs-measured gate.

Import surface is deliberately lazy-friendly: :mod:`repro.obs.trace` has no
repro dependencies (executors import it freely), :mod:`repro.obs.export`
depends only on trace, and :mod:`repro.obs.model_check` imports the planner
lazily so ``python -m repro.obs.model_check`` can set fake-device flags
before jax initializes.
"""

from .trace import (  # noqa: F401
    ExchangeEdge,
    QueryTrace,
    Span,
    Tracer,
    deposit,
    maybe_span,
    model_error,
    span_args,
)

__all__ = [
    "ExchangeEdge",
    "QueryTrace",
    "Span",
    "Tracer",
    "deposit",
    "maybe_span",
    "model_error",
    "span_args",
]
