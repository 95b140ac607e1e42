"""Model-vs-measured: per-edge error ratios between the planner/autotuner
and what the devices actually did.

The paper's cost model is only worth its exchange placements if its inputs
survive contact with data (Rödiger §3.1 prices every exchange; §6 checks
the prices).  For every traced shuffle edge this module compares

* **bytes** — the planner's modeled wire bytes
  (:meth:`PhysicalPlan.exchange_summary`, the §3.1 ``exchange_bytes``
  formula over catalog capacities) against the MEASURED arrivals (the
  psum'd destination histogram priced with the same (n-1)/n wire rule),
  as ``byte_model_err = max(modeled/measured, measured/modeled)``.  This
  ratio is deterministic for a given dataset and hardware-independent, so
  CI gates it at the same 2x bound ``bench_autotune`` applies to its
  makespan model.

* **time** — the autotuner's predicted makespan for the edge
  (:func:`repro.core.autotune.exchange_makespan` under the plan's tuned
  knobs) against the run's measured wall time, apportioned over edges by
  predicted share.  On CPU fake devices this ratio is surfaced but NOT
  gated: the model prices TPU ICI links, so only a
  :func:`~repro.core.autotune.calibrate_chip`-calibrated chip makes the
  2x bar meaningful (the ROADMAP's real-hardware item records into
  exactly this field).

``python -m repro.obs.model_check --query q17 --shards 8 --streamed``
runs one traced query on fake devices and prints the JSON report —
``bench_tpch`` shells out to it for the measured column, and the
OBSERVABILITY doc's executable block is a variant of it.
"""

from __future__ import annotations

from typing import Mapping

from .trace import ExchangeEdge, QueryTrace

__all__ = [
    "edge_models",
    "build_query_trace",
    "model_report",
    "assert_bytes_within",
    "BYTE_MODEL_BOUND",
]

# The CI bound on byte_model_err — same 2x bar bench_autotune asserts for
# its makespan model.
BYTE_MODEL_BOUND = 2.0


def edge_models(plan) -> dict[str, dict]:
    """Per-shuffle-edge model predictions, keyed like the runtime reports.

    Walks the plan's shuffle edges in :func:`_report_keys` order (the same
    stable ``shuffle[<col>]#<ordinal>`` keys the executor reports under)
    and prices each one: modeled wire bytes via the planner's own
    ``_wire_bytes`` and predicted makespan via ``exchange_makespan`` with
    the plan's tuned knobs.  Tuned chunk counts that do not divide an
    edge's row count fall back to unchunked — the same fallback
    ``hash_shuffle`` itself applies.
    """
    from ..core.autotune import exchange_makespan
    from ..relational.planner.executor import _report_keys
    from ..relational.planner.physical import PlannerConfig, exchange_bytes

    keys = _report_keys(plan.root)
    n_inner = plan.num_shards // max(plan.num_pods, 1)
    tuned = plan.tuned
    out: dict[str, dict] = {}

    def walk(n, seen):
        if id(n) in seen:
            return
        seen.add(id(n))
        for c in n.children:
            walk(c, seen)
        if n.kind != "exchange" or n.info["exkind"] != "shuffle":
            return
        st = n.info["stats"]
        # Bytes are priced on the rows the estimator expects to FLOW
        # (``est_rows`` — selectivity/containment-aware when the plan saw a
        # profile), not the buffer capacity: streamed plans cap ``stats`` at
        # one morsel-step, and a selective filter/join upstream means far
        # fewer valid rows than capacity.  The makespan prediction below
        # keeps the capacity stats — that is what the autotuner priced.
        est_rows = n.info.get("est_rows") or st.rows * plan.num_shards
        modeled = exchange_bytes(
            "partition", int(round(est_rows)), 0, st.row_bytes,
            PlannerConfig(num_units=plan.num_shards),
        )
        try:
            predicted = exchange_makespan(
                st, n_inner, impl=tuned.impl, pack_impl=tuned.pack_impl,
                pipeline_chunks=tuned.pipeline_chunks,
                transport_chunks=tuned.transport_chunks,
                num_pods=plan.num_pods,
            )
        except AssertionError:  # chunk knobs don't divide this edge's rows
            predicted = exchange_makespan(
                st, n_inner, impl=tuned.impl, pack_impl=tuned.pack_impl,
                pipeline_chunks=1, transport_chunks=1,
                num_pods=plan.num_pods,
            )
        out[keys[id(n)]] = dict(
            rows=int(round(est_rows)),
            row_bytes=int(st.row_bytes),
            modeled_wire_bytes=int(modeled),
            predicted_s=float(predicted),
        )

    walk(plan.root, set())
    return out


def _wire_fraction(num_shards: int) -> float:
    """A hash-routed row crosses the wire iff it leaves its shard:
    probability (n-1)/n — the planner's own partition-bytes rule."""
    return (num_shards - 1) / num_shards if num_shards > 1 else 0.0


def build_query_trace(
    plan,
    reports: Mapping[str, Mapping],
    models: Mapping[str, Mapping] | None = None,
    counters: Mapping[str, float] | None = None,
    measured_s: float | None = None,
) -> QueryTrace:
    """Assemble one run's :class:`QueryTrace` from the fetched device
    reports plus the plan's edge models.

    ``reports`` maps edge keys to the executor's per-shuffle report
    (``counts``/``overload``/``plain_overload``/``salted``; ``counts`` is
    the arrival histogram then the cross-pod and in-pod row counts, see
    ``executor._shuffle_histogram``).  Streamed runs
    key multi-pass traversals as ``<edge>@p<pass>`` — the base edge's
    model applies to each traversal (every pass re-ships the rows).
    ``measured_s`` (dispatch-to-fetched wall) is apportioned over edges by
    predicted share.
    """
    import numpy as np

    models = edge_models(plan) if models is None else models
    frac = _wire_fraction(plan.num_shards)
    edges = []
    preds = []
    for key in reports:
        base = key.split("@p")[0]
        preds.append((models.get(base) or {}).get("predicted_s") or 0.0)
    total_pred = sum(preds) or float(len(reports) or 1)
    for (key, rep), pred in zip(reports.items(), preds):
        base = key.split("@p")[0]
        m = models.get(base) or {}
        counts = np.asarray(rep["counts"]).astype(np.int64)
        hist = counts[:plan.num_shards]
        crosspod_rows, inpod_rows = counts[plan.num_shards:]
        rows_arrived = int(hist.sum())
        row_bytes = int(m.get("row_bytes") or 0)
        traversals = int(rep.get("traversals", 1) or 1)
        share = (
            pred / total_pred if total_pred else 1.0 / max(len(reports), 1)
        )
        edges.append(
            ExchangeEdge(
                key=key,
                rows=int(m.get("rows") or 0),
                row_bytes=row_bytes,
                hist=tuple(int(x) for x in hist),
                measured_bytes=int(rows_arrived * row_bytes * frac),
                crosspod_bytes=int(crosspod_rows) * row_bytes,
                inpod_bytes=int(inpod_rows) * row_bytes,
                modeled_wire_bytes=(
                    int(m.get("modeled_wire_bytes") or 0) * traversals
                ),
                traversals=traversals,
                overload=float(rep["overload"]),
                plain_overload=float(rep["plain_overload"]),
                salted=bool(rep["salted"]),
                predicted_s=m.get("predicted_s"),
                measured_s=(
                    measured_s * share if measured_s is not None else None
                ),
            )
        )
    return QueryTrace(
        query=plan.name,
        num_shards=plan.num_shards,
        num_pods=plan.num_pods,
        edges=tuple(edges),
        counters=dict(counters or {}),
        measured_s=measured_s,
    )


def model_report(qt: QueryTrace) -> dict:
    """Flat model-error summary for one run (benchmarks emit this):
    per-edge byte/time error ratios plus the worst byte ratio — the
    number CI's ``--compare`` gate watches (lower is better, >= 1)."""
    per_edge = {
        e.key: dict(
            measured_bytes=e.measured_bytes,
            modeled_wire_bytes=e.modeled_wire_bytes,
            byte_model_err=e.byte_model_err,
            predicted_s=e.predicted_s,
            measured_s=e.measured_s,
            time_model_err=e.time_model_err,
        )
        for e in qt.edges
    }
    byte_errs = [e.byte_model_err for e in qt.edges if e.byte_model_err]
    return dict(
        query=qt.query,
        edges=per_edge,
        worst_byte_model_err=max(byte_errs) if byte_errs else None,
    )


def assert_bytes_within(qt: QueryTrace, bound: float = BYTE_MODEL_BOUND) -> None:
    """Raise if any edge's measured wire bytes disagree with the planner's
    model by more than ``bound``x (edges that shipped zero rows are
    vacuous)."""
    for e in qt.edges:
        err = e.byte_model_err
        if err is not None and err > bound:
            raise AssertionError(
                f"{qt.query} {e.key}: measured {e.measured_bytes}B vs "
                f"modeled {e.modeled_wire_bytes}B wire bytes — "
                f"{err:.2f}x exceeds the {bound}x model bound"
            )


# ---------------------------------------------------------------------------
# CLI: one traced query on fake devices, report as JSON.
# ---------------------------------------------------------------------------


def _cli_run(args) -> dict:
    # Import order matters: the fake-device flag must precede jax init.
    import os

    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.shards}",
    )
    from repro.obs import export as obs_export
    from repro.obs.trace import Tracer
    from repro.relational import datagen
    from repro.relational import stats as rstats
    from repro.relational.context import ExecutionContext, StatsMode
    from repro.relational.planner import tpch as T

    tabs = datagen.gen_all(args.sf)
    pq = T.ALL_QUERIES[args.query]()
    tables = {t: tabs[t] for t in pq.tables}
    morsel_rows = args.morsel_rows
    if args.streamed and not morsel_rows:
        morsel_rows = max(tabs["lineitem"].capacity // 4, 1)
    tracer = Tracer()
    # Plan from a data profile: the byte model prices the rows the
    # estimator expects to flow, which is only meaningful when the
    # estimator has seen the data (selectivities, key ndv).
    ctx = ExecutionContext(
        num_shards=args.shards, num_pods=args.pods,
        morsel_rows=morsel_rows or None, trace=tracer,
        stats_mode=StatsMode.PROFILE,
        stats_profile=rstats.collect_stats(tables),
    )
    result = T.run_query(pq, tables, ctx)
    qt = tracer.query_traces[-1] if tracer.query_traces else None
    rep = model_report(qt) if qt is not None else {"query": args.query}
    try:
        rep["result"] = float(result)
    except (TypeError, ValueError):
        rep["result"] = None
    rep["span_names"] = sorted(
        {s.name.split(":")[0] for root in tracer.spans for s in root.walk()}
    )
    if args.trace_dir:
        rep["trace_path"] = obs_export.write_trace_dir(
            tracer, args.trace_dir, basename=f"model_check-{args.query}"
        )
    if qt is not None and args.bound > 0:
        assert_bytes_within(qt, args.bound)
    return rep


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="run one traced TPC-H query and report model-vs-measured"
    )
    ap.add_argument("--query", default="q17")
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--streamed", action="store_true",
                    help="stream lineitem morsel-by-morsel (out of core)")
    ap.add_argument("--morsel-rows", type=int, default=0)
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--bound", type=float, default=BYTE_MODEL_BOUND,
                    help="fail if byte_model_err exceeds this (0 disables)")
    args = ap.parse_args(argv)
    print(json.dumps(_cli_run(args), indent=2, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
