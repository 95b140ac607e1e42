"""The telemetry spine: spans, counters, QueryTrace, export, model gate.

End-to-end tracing on real multi-device runs lives in the
``traced_query`` / ``trace_bit_identical`` scenarios of
``tests/_multidev_driver.py`` and the merged-timeline scenario of
``tests/_multiproc_driver.py``; this file covers the host-side pieces
that need no devices — span nesting and thread-safety, the JSON and
Chrome trace-event exports, the QueryTrace round-trip, ``deposit``, and
the model-error arithmetic the CI gate runs on.
"""

import json
import threading

import pytest

from repro.obs.export import (
    chrome_trace_events,
    merge_trace_dir,
    query_trace_from_json,
    query_trace_to_json,
    tracer_to_dict,
    write_trace_dir,
)
from repro.obs.model_check import (
    BYTE_MODEL_BOUND,
    assert_bytes_within,
    model_report,
)
from repro.obs.trace import (
    ExchangeEdge,
    QueryTrace,
    Tracer,
    deposit,
    maybe_span,
    model_error,
    span_args,
)


def _edge(key="shuffle[k]#0", measured=900, modeled=1000, **kw) -> ExchangeEdge:
    defaults = dict(
        key=key, rows=100, row_bytes=12, hist=(25, 25, 25, 25),
        measured_bytes=measured, modeled_wire_bytes=modeled,
        overload=1.2, plain_overload=1.2, salted=False,
        predicted_s=1e-4, measured_s=2e-4,
    )
    defaults.update(kw)
    return ExchangeEdge(**defaults)


def _qt(*edges, query="q17") -> QueryTrace:
    return QueryTrace(
        query=query, num_shards=4, num_pods=1, edges=tuple(edges),
        counters={"morsels": 4.0, "passes": 2.0}, measured_s=0.5,
    )


# ---------------------------------------------------------------------------
# model_error: the one ratio everything gates on.
# ---------------------------------------------------------------------------

def test_model_error_symmetric_and_lower_bounded():
    assert model_error(2.0, 1.0) == model_error(1.0, 2.0) == 2.0
    assert model_error(3.0, 3.0) == 1.0
    assert model_error(None, 1.0) is None
    assert model_error(1.0, 0.0) is None  # zero-byte edges are vacuous


def test_assert_bytes_within():
    assert_bytes_within(_qt(_edge(measured=900, modeled=1000)))
    with pytest.raises(AssertionError, match="exceeds the 2.0x"):
        assert_bytes_within(_qt(_edge(measured=100, modeled=1000)))
    # a custom bound and the vacuous zero-row edge
    assert_bytes_within(_qt(_edge(measured=100, modeled=1000)), bound=10.0)
    assert_bytes_within(_qt(_edge(measured=0, modeled=1000)))
    assert BYTE_MODEL_BOUND == 2.0


def test_model_report_worst_edge():
    rep = model_report(_qt(
        _edge(key="a", measured=1000, modeled=1000),
        _edge(key="b", measured=500, modeled=900),
    ))
    assert rep["query"] == "q17"
    assert rep["edges"]["a"]["byte_model_err"] == 1.0
    assert rep["worst_byte_model_err"] == pytest.approx(1.8)


# ---------------------------------------------------------------------------
# Span nesting.
# ---------------------------------------------------------------------------

def test_spans_nest_and_close():
    tr = Tracer(pid=0)
    with tr.span("repro.plan", cat="plan", query="q17"):
        with tr.span("repro.build", cat="compile", streamed=True):
            pass
        with tr.span("repro.execute", cat="execute"):
            with maybe_span(tr, "repro.fetch", "execute", bytes=42):
                pass
    assert len(tr.spans) == 1  # one root
    root = tr.spans[0]
    assert [s.name for s in root.walk()] == [
        "repro.plan", "repro.build", "repro.execute", "repro.fetch"
    ]
    assert all(s.dur is not None for s in root.walk())
    assert root.children[0].args == {"streamed": True}
    assert root.children[1].children[0].args == {"bytes": 42}


def test_maybe_span_is_noop_without_tracer():
    with maybe_span(None, "anything") as s:
        assert s is None


def test_span_args_reach_nested_spans():
    tr = Tracer(pid=0)
    with span_args(req=7, query="q1"):
        with tr.span("repro.plan", query="q6"):  # an explicit arg wins
            with span_args(tenant="a"):
                with tr.span("repro.fetch"):
                    pass
    with tr.span("repro.round"):
        pass
    plan, rnd = tr.spans
    assert plan.args == {"req": 7, "query": "q6"}
    assert plan.children[0].args == {"req": 7, "query": "q1", "tenant": "a"}
    assert rnd.args == {}


def test_spans_from_threads_do_not_interleave():
    """The span stack is thread-local: two threads tracing concurrently
    each build their own root — never nest under each other."""
    tr = Tracer(pid=0)
    barrier = threading.Barrier(2)

    def work(i):
        barrier.wait()
        with tr.span(f"root:{i}"):
            with tr.span(f"child:{i}"):
                pass

    ts = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert sorted(s.name for s in tr.spans) == ["root:0", "root:1"]
    for root in tr.spans:
        i = root.name.split(":")[1]
        assert [c.name for c in root.children] == [f"child:{i}"]


def test_counters_gauges_histograms():
    tr = Tracer(pid=0)
    tr.counter("runs")
    tr.counter("runs", 2.0)
    tr.gauge("depth", 3.0)
    tr.observe("wait_s", 0.1)
    tr.observe("wait_s", 0.3)
    assert tr.counters["runs"] == 3.0
    assert tr.gauges["depth"] == 3.0
    assert tr.histograms["wait_s"] == [0.1, 0.3]


# ---------------------------------------------------------------------------
# deposit: QueryTrace -> tracer record + counters.
# ---------------------------------------------------------------------------

def test_deposit_lays_out_edges_and_counters():
    tr = Tracer(pid=0)
    qt = _qt(_edge(key="a"), _edge(key="b", measured=300, modeled=400))
    deposit(tr, qt)
    # the record keeps every edge; no span is made up for it
    assert tr.query_traces == [qt]
    assert [e.key for e in tr.query_traces[0].edges] == ["a", "b"]
    assert tr.spans == []
    assert tr.counters["exchange.measured_bytes"] == 1200.0
    assert tr.counters["exchange.modeled_wire_bytes"] == 1400.0
    assert tr.counters["query.q17.runs"] == 1.0
    assert tr.counters["query.q17.morsels"] == 4.0
    deposit(None, qt)  # no-op without a tracer


# ---------------------------------------------------------------------------
# JSON round-trip.
# ---------------------------------------------------------------------------

def test_query_trace_json_roundtrip():
    qt = _qt(_edge(key="a"), _edge(key="b", salted=True, traversals=4))
    assert query_trace_from_json(query_trace_to_json(qt)) == qt


def test_query_trace_roundtrip_defaults_traversals():
    """Traces written before the traversal counter existed still load."""
    d = json.loads(query_trace_to_json(_qt(_edge())))
    for e in d["edges"]:
        del e["traversals"]
    loaded = query_trace_from_json(json.dumps(d))
    assert loaded.edges[0].traversals == 1


# ---------------------------------------------------------------------------
# Chrome trace-event (Perfetto) validity.
# ---------------------------------------------------------------------------

def _traced_tracer() -> Tracer:
    tr = Tracer(pid=0)
    with tr.span("repro.plan", cat="plan", query="q17"):
        with tr.span("repro.build", cat="compile"):
            pass
    deposit(tr, _qt(_edge(key="a"), _edge(key="b")))
    return tr


def test_chrome_events_sorted_and_matched():
    events = chrome_trace_events(_traced_tracer())
    meta = [e for e in events if e["ph"] == "M"]
    dur = [e for e in events if e["ph"] in ("B", "E")]
    assert meta and meta[0]["name"] == "process_name"
    # timestamps are sorted non-decreasing
    ts = [e["ts"] for e in dur]
    assert ts == sorted(ts)
    # B/E counts match per (name, pid, tid) and never go negative
    depth: dict = {}
    for e in dur:
        k = (e["name"], e["pid"], e["tid"])
        depth[k] = depth.get(k, 0) + (1 if e["ph"] == "B" else -1)
        assert depth[k] >= 0, f"E before B for {k}"
    assert all(v == 0 for v in depth.values()), depth


def test_tracer_to_dict_is_perfetto_loadable_json():
    d = tracer_to_dict(_traced_tracer(), process_name="proc 0")
    s = json.dumps(d)  # jsonable end to end
    loaded = json.loads(s)
    assert loaded["traceEvents"][0]["args"]["name"] == "proc 0"
    assert loaded["displayTimeUnit"] == "ms"
    assert loaded["queryTraces"][0]["query"] == "q17"


# ---------------------------------------------------------------------------
# Per-process files + merge (the 2-process Gloo scenario drives the real
# thing; this covers the file plumbing single-process).
# ---------------------------------------------------------------------------

def test_write_and_merge_trace_dir(tmp_path):
    d = str(tmp_path)
    for pid in (0, 1):
        tr = Tracer(pid=pid)
        with tr.span(f"work:p{pid}"):
            pass
        tr.counter("runs", 1.0)
        path = write_trace_dir(tr, d, basename="t")
        assert path.endswith(f"t-p{pid}.json")
    merged = merge_trace_dir(d, basename="t", out=f"{d}/merged.json")
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {0, 1}
    assert merged["counters"]["runs"] == 2.0
    # metadata first, then time-sorted events
    phs = [e["ph"] for e in merged["traceEvents"]]
    assert phs[:2] == ["M", "M"]
    with open(f"{d}/merged.json") as f:
        assert json.load(f) == merged
    with pytest.raises(FileNotFoundError):
        merge_trace_dir(d, basename="nope")
