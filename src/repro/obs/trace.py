"""One telemetry spine: spans on the profiler's clock + per-query device counters.

* Spans — :func:`maybe_span` and :meth:`Tracer.span` always enter a
  ``jax.profiler.TraceAnnotation``, so every span lands in the JAX
  profiler's trace when a profiler session is on, on the same clock as the
  device's ops; a gap in the device timeline can then be read against
  what the host was doing.  With no session the annotation costs about a
  microsecond.  Names are stable and start with ``repro.``
  (``repro.round``, ``repro.plan``, ``repro.fetch``, ...); round numbers,
  template names and request ids go in the args, never in the name.
  :func:`span_args` adds args to every span opened inside it (the serving
  engine tags each request's stages with ``req``, ``query`` and
  ``tenant``).

* :class:`Tracer` — attach one via the frozen ``ExecutionContext.trace``
  knob and every span is also kept in memory (nested per thread), next to
  a thread-safe registry of counters, gauges and histograms; this is what
  :mod:`repro.obs.export` writes as a Perfetto timeline.  The field is
  ``compare=False`` so a traced and an untraced context hash equal —
  tracing never invalidates a plan-cache or executor-memo entry, and never
  changes what runs inside the jit (device counters are ALWAYS on; the
  tracer only decides whether anyone writes them down).

* :class:`QueryTrace` — the per-run record of what the devices measured:
  one :class:`ExchangeEdge` per shuffle (destination histogram psum'd
  inside the jit, measured vs modeled wire bytes, the autotuner's
  predicted makespan, salted/plain decision) plus the streamed path's
  spill/drain/prefetch counters.  Returned per-run from
  ``runner.collect(out)``; :func:`deposit` files it and its byte counters
  into a tracer.

In-memory span timestamps are wall-clock epoch seconds (``time.time``),
the clock the profiler stamps its events with, so traces from different
processes of one Gloo cluster merge onto one timeline and line up with a
profiler trace of the same run.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import threading
import time
from typing import Any, Iterator, Mapping

from jax.profiler import TraceAnnotation

__all__ = [
    "Span",
    "Tracer",
    "ExchangeEdge",
    "QueryTrace",
    "maybe_span",
    "span_args",
    "model_error",
    "deposit",
]


def _process_index() -> int:
    """This process's track id — ``jax.process_index()`` when jax is up
    (multi-process Gloo runs), else 0.  Resolved lazily so a Tracer can be
    built before ``jax.distributed`` initializes."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


def model_error(predicted: float | None, measured: float | None) -> float | None:
    """Symmetric model-error ratio: ``max(pred/meas, meas/pred)`` — always
    >= 1, lower is better, 1.0 = the model was exact.  The same score
    ``bench_autotune`` gates at 2x.  ``None`` (or a non-positive side) means
    no comparison is possible."""
    if predicted is None or measured is None:
        return None
    if predicted <= 0.0 or measured <= 0.0:
        return None
    return max(predicted / measured, measured / predicted)


# ---------------------------------------------------------------------------
# Spans.
# ---------------------------------------------------------------------------

# What a span opened in this context inherits: the tracer of the enclosing
# traced span, and the args of the enclosing :func:`span_args` blocks.
_INHERITED: contextvars.ContextVar[tuple["Tracer | None", dict]] = (
    contextvars.ContextVar("repro_span_context", default=(None, {}))
)


@contextlib.contextmanager
def span_args(**args: Any):
    """Add ``args`` to every span opened inside the ``with`` block, on this
    thread (the serving engine tags a request's stages this way, so spans
    opened deep in the executor name the request too)."""
    tracer, inherited = _INHERITED.get()
    token = _INHERITED.set((tracer, {**inherited, **args}))
    try:
        yield
    finally:
        _INHERITED.reset(token)


def _annotation(name: str, args: dict) -> TraceAnnotation:
    """The profiler event of a span: scalar args only."""
    return TraceAnnotation(
        name,
        **{k: v for k, v in args.items() if isinstance(v, (str, int, float))},
    )


@dataclasses.dataclass
class Span:
    """One timed region.  ``t0`` is epoch seconds; ``dur`` is None while
    the span is open.  ``pid``/``tid`` are the Chrome trace-event track
    ids (process index / thread ident)."""

    name: str
    cat: str
    t0: float
    dur: float | None
    pid: int
    tid: int
    args: dict
    children: list["Span"] = dataclasses.field(default_factory=list)

    def walk(self) -> Iterator["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()


class Tracer:
    """Thread-safe span + metric registry.

    Spans nest per-thread (a ``threading.local`` stack); finished root
    spans land in ``self.spans``.  Counters/gauges/histograms are plain
    dicts under one lock — cheap enough to leave on in benchmarks.
    ``query_traces`` accumulates every :class:`QueryTrace` deposited by a
    traced run, in completion order.
    """

    def __init__(self, pid: int | None = None):
        self._pid = pid
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.spans: list[Span] = []
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.histograms: dict[str, list[float]] = {}
        self.query_traces: list["QueryTrace"] = []

    @property
    def pid(self) -> int:
        if self._pid is None:
            self._pid = _process_index()
        return self._pid

    def _stack(self) -> list[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host", **args: Any):
        """Open a nested span around a ``with`` block: kept in memory and
        entered as a profiler annotation of the same name and args.  Spans
        opened inside it without a tracer are kept by this one too."""
        _, inherited = _INHERITED.get()
        args = {**inherited, **args}
        s = Span(
            name=name, cat=cat, t0=time.time(), dur=None,
            pid=self.pid, tid=threading.get_ident(), args=args,
        )
        stack = self._stack()
        if stack:
            stack[-1].children.append(s)
        else:
            with self._lock:
                self.spans.append(s)
        stack.append(s)
        token = _INHERITED.set((self, inherited))
        t0 = time.perf_counter()
        try:
            with _annotation(name, args):
                yield s
        finally:
            s.dur = time.perf_counter() - t0
            _INHERITED.reset(token)
            stack.pop()

    # -- metrics ------------------------------------------------------------

    def counter(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self.gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self.histograms.setdefault(name, []).append(float(value))

    # -- query traces ---------------------------------------------------------

    def add_query_trace(self, qt: "QueryTrace") -> None:
        with self._lock:
            self.query_traces.append(qt)


@contextlib.contextmanager
def maybe_span(tracer: Tracer | None, name: str, cat: str = "host", **args):
    """A span around a ``with`` block: ``tracer.span(...)`` with ``tracer``,
    or else with the tracer of the enclosing traced span; with neither, the
    profiler annotation alone (about a microsecond when no profiler session
    is on).  Yields the in-memory :class:`Span`, or None without a tracer."""
    inherited_tracer, inherited = _INHERITED.get()
    tracer = tracer if tracer is not None else inherited_tracer
    if tracer is None:
        with _annotation(name, {**inherited, **args}):
            yield None
        return
    with tracer.span(name, cat=cat, **args) as s:
        yield s


# ---------------------------------------------------------------------------
# The per-run device-counter record.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExchangeEdge:
    """What one shuffle edge measured, next to what the model predicted.

    ``hist`` is the psum'd per-destination arrival histogram (valid rows,
    the exact routing rule of the exchange).  ``measured_bytes`` prices the
    arrivals with the planner's own wire formula (rows x row_bytes x
    (n-1)/n — a row crosses the wire iff it leaves its shard), so the
    ratio against ``modeled_wire_bytes`` isolates the planner's ROW
    estimate.  ``predicted_s`` is the autotuner's makespan for this edge's
    stats under the plan's tuned knobs; ``measured_s`` the edge's share of
    the run's measured wall time (apportioned by predicted share — per-edge
    device timestamps need a profiler, not a counter).

    ``crosspod_bytes`` and ``inpod_bytes`` split the traffic by network
    level, counted exactly on the devices from the exchange's routing rule:
    the valid rows whose destination pod differs from the pod they start in
    (0 on a single-pod mesh), and the rows the in-pod hop moves to another
    chip of the pod they are in, each times ``row_bytes``.
    """

    key: str
    rows: int                    # estimated rows flowing per traversal
    row_bytes: int
    hist: tuple[int, ...]
    measured_bytes: int
    modeled_wire_bytes: int
    overload: float              # measured max/fair-share of the chosen route
    plain_overload: float        # measured overload of the plain-hash route
    salted: bool                 # did the runtime gate pick the salted route
    predicted_s: float | None = None
    measured_s: float | None = None
    # How many times this edge shipped its input during the traversal the
    # report covers: 1 for in-memory edges and streamed-side edges (the
    # morsel steps sum to one pass over the stream), the morsel-step count
    # for a resident-side edge inside a streamed pass (the evaluator
    # re-ships the unchanged table every step).  ``modeled_wire_bytes``
    # already includes the multiplier — the byte model prices one shipment.
    traversals: int = 1
    crosspod_bytes: int = 0      # rows that change pod x row_bytes
    inpod_bytes: int = 0         # rows that change chip inside a pod x row_bytes

    @property
    def byte_model_err(self) -> float | None:
        """max(modeled/measured, measured/modeled) wire bytes, >= 1."""
        return model_error(
            float(self.modeled_wire_bytes), float(self.measured_bytes)
        )

    @property
    def time_model_err(self) -> float | None:
        return model_error(self.predicted_s, self.measured_s)

    def legacy_report(self) -> dict:
        """The old ``run.exchange_report`` entry shape for this edge."""
        import numpy as np

        return {
            "hist": np.asarray(self.hist, dtype=np.int64),
            "overload": float(self.overload),
            "plain_overload": float(self.plain_overload),
            "salted": bool(self.salted),
        }


@dataclasses.dataclass(frozen=True)
class QueryTrace:
    """One run's worth of device-side measurement, under one record.

    ``counters`` carries whatever the execution path counted host-side:
    the streamed runner's ``passes``/``morsels``/``spilled_rows``/
    ``drain_rounds``/``prefetch_*`` stats land here verbatim; the
    in-memory executor contributes nothing beyond the edges.
    """

    query: str
    num_shards: int
    num_pods: int
    edges: tuple[ExchangeEdge, ...] = ()
    counters: Mapping[str, float] = dataclasses.field(default_factory=dict)
    measured_s: float | None = None   # dispatch-to-fetched wall time

    def exchange_report(self) -> dict:
        """The legacy ``run.exchange_report`` dict view."""
        return {e.key: e.legacy_report() for e in self.edges}

    def model_errors(self) -> dict[str, dict]:
        """Per-edge model-error ratios (``obs.model_check`` gates these)."""
        return {
            e.key: {
                "byte_model_err": e.byte_model_err,
                "time_model_err": e.time_model_err,
            }
            for e in self.edges
        }


def deposit(tracer: Tracer | None, qt: QueryTrace) -> None:
    """File one run's QueryTrace in a tracer: the record itself and its
    byte counters (``exchange.measured_bytes``, ``.modeled_wire_bytes``,
    ``.crosspod_bytes``, ``.inpod_bytes``) and run counters.  No-op without
    a tracer."""
    if tracer is None:
        return
    tracer.add_query_trace(qt)
    for e in qt.edges:
        tracer.counter("exchange.measured_bytes", e.measured_bytes)
        tracer.counter("exchange.modeled_wire_bytes", e.modeled_wire_bytes)
        tracer.counter("exchange.crosspod_bytes", e.crosspod_bytes)
        tracer.counter("exchange.inpod_bytes", e.inpod_bytes)
    tracer.counter(f"query.{qt.query}.runs", 1.0)
    for k, v in qt.counters.items():
        if isinstance(v, (int, float)):
            tracer.counter(f"query.{qt.query}.{k}", float(v))
