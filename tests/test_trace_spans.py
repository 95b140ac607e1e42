"""The serving path's spans on the JAX profiler's trace, and the names the
device programs carry.

A profiler trace is recorded on the CPU around one ``QueryServeEngine.serve``
round of Q1 and Q6 (tiny scale factor) and read back with
``jax.profiler.ProfileData``: every request's stages appear in order inside
``repro.round``, tagged with its request id; each device-to-host read is one
``repro.transfer``; and the spans a ``Tracer`` keeps in memory are the
profiler's events, on the same clock.  Q3's compiled program is named after
its template and its ops after their operators.
"""

import dataclasses
import re
import warnings

import jax
import pytest

from repro.obs.trace import Tracer
from repro.relational import datagen
from repro.relational.context import ExecutionContext
from repro.relational.planner import tpch
from repro.relational.planner.executor import compile_plan
from repro.serve import QueryRequest, QueryServeEngine

SF = 0.002
STAGES = ["repro.plan", "repro.dispatch", "repro.wait", "repro.fetch",
          "repro.finalize"]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int  # epoch ns
    end: int
    args: tuple

    @property
    def arg(self) -> dict:
        return dict(self.args)


def profiler_events(trace_dir) -> list[Event]:
    """The ``repro.*`` host events of the one trace under ``trace_dir``, on
    the epoch clock (the profiler stamps events from the session's start)."""
    from jax.profiler import ProfileData

    (path,) = list(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    profile = ProfileData.from_file(str(path))
    out = []
    # jaxlib builds the stats' types on first use, warning that they lack a
    # __module__, which the suite's -W error would turn into an abort
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        env = profile.find_plane_with_name("Task Environment")
        t0 = int(dict(env.stats)["profile_start_time"])
        for plane in profile.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        start = t0 + int(e.start_ns)
                        out.append(Event(
                            e.name, start, start + int(e.duration_ns),
                            tuple(sorted(e.stats)),
                        ))
    return sorted(out, key=lambda e: e.start)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced serve round of Q1 and Q6 after a warm-up round."""
    tabs = datagen.gen_all(SF)
    templates = [tpch.q1(), tpch.q6()]
    tracer = Tracer(pid=0)
    engine = QueryServeEngine(
        {"lineitem": tabs["lineitem"]},
        ExecutionContext(num_shards=1, trace=tracer),
        num_slots=2, templates=templates,
    )
    engine.serve([QueryRequest("warm", pq) for pq in templates])
    tracer.spans.clear()
    trace_dir = tmp_path_factory.mktemp("profile")
    requests = [QueryRequest("a", templates[0]), QueryRequest("b", templates[1])]
    jax.profiler.start_trace(str(trace_dir))
    try:
        done = engine.serve(requests)
    finally:
        jax.profiler.stop_trace()
    return done, tracer, profiler_events(trace_dir)


def test_request_stages_in_order_under_the_round(served):
    done, _, events = served
    (rnd,) = [e for e in events if e.name == "repro.round"]
    assert rnd.arg["admitted"] == 2 and rnd.arg["queued"] == 0
    assert {r.req_id for r in done} == {2, 3}  # the warm-up round took 0, 1
    for r in done:
        mine = [e for e in events if e.arg.get("req") == r.req_id]
        stages = [e for e in mine if e.name in STAGES]
        assert [e.name for e in stages] == STAGES, [e.name for e in mine]
        assert all(rnd.start <= e.start and e.end <= rnd.end for e in mine)
        assert all(a.end <= b.start for a, b in zip(stages, stages[1:]))
        assert {e.arg["query"] for e in mine} == {r.query.name}
        assert {e.arg["tenant"] for e in mine} == {r.tenant}
    # a warm round builds nothing
    assert not [e for e in events if e.name == "repro.build"]


def test_one_transfer_per_device_read(served):
    _, _, events = served
    transfers = [e for e in events if e.name == "repro.transfer"]
    by_query = {}
    for e in transfers:
        by_query[e.arg["query"]] = by_query.get(e.arg["query"], 0) + 1
    # the drop count, then Q1's six aggregates and Q6's one
    assert by_query == {"q1": 7, "q6": 2}
    fetches = [e for e in events if e.name == "repro.fetch"]
    for e in transfers:
        (inside,) = [f for f in fetches if f.arg["req"] == e.arg["req"]]
        assert inside.start <= e.start and e.end <= inside.end
        assert e.arg["bytes"] > 0


def test_in_memory_spans_are_the_profiler_events(served):
    _, tracer, events = served
    kept = [s for root in tracer.spans for s in root.walk()]
    assert [s.name for s in tracer.spans] == ["repro.round"]
    assert {s.name for s in kept} == {"repro.round", "repro.transfer", *STAGES}
    unmatched = list(events)
    for s in kept:
        want = {k: v for k, v in s.args.items() if isinstance(v, (str, int))}
        match = [e for e in unmatched if e.name == s.name and e.arg == want]
        assert match, (s.name, s.args)
        nearest = min(match, key=lambda e: abs(e.start - s.t0 * 1e9))
        assert abs(nearest.start - s.t0 * 1e9) < 1e6  # within 1 ms
        unmatched.remove(nearest)


def test_q3_program_and_ops_are_named():
    tabs = datagen.gen_all(SF)
    pq = tpch.q3()
    tables = {t: tabs[t] for t in pq.tables}
    plan = pq.plan({t: tables[t].capacity for t in pq.tables}, 1)
    run = compile_plan(plan, tables)
    lowered = run._jfn.lower(*run._flat)
    assert re.search(r"module @jit_q3\b", lowered.as_text())
    hlo = lowered.compile().as_text()
    assert hlo.startswith("HloModule jit_q3,")
    scopes = {m.split("/")[1] for m in re.findall(r'op_name="(jit\(q3\)/[^"]*)"', hlo)}
    assert {"join_pk", "groupby_sorted", "topk"} <= scopes, scopes
    assert scopes <= {"scan", "filter", "project", "join_pk", "groupby_sorted",
                      "topk", "shuffle", "broadcast"}, scopes


def test_q1_dense_groupby_scope_names_its_path():
    """Q1's six groups reduce by compare-and-reduce, and the ops say so."""
    tabs = datagen.gen_all(SF)
    pq = tpch.q1()
    tables = {t: tabs[t] for t in pq.tables}
    plan = pq.plan({t: tables[t].capacity for t in pq.tables}, 1)
    run = compile_plan(plan, tables)
    hlo = run._jfn.lower(*run._flat).compile().as_text()
    paths = {
        m.split("/")[2]
        for m in re.findall(r'op_name="(jit\(q1\)/groupby_dense/[^"]*)"', hlo)
    }
    assert "compare" in paths and "scatter" not in paths, paths


def test_q3_join_scopes_name_their_probe():
    """Q3's joins probe by the merge, under ``join_pk/probe_merge``: no op
    there is a loop of dependent binary-search passes."""
    tabs = datagen.gen_all(SF)
    pq = tpch.q3()
    tables = {t: tabs[t] for t in pq.tables}
    plan = pq.plan({t: tables[t].capacity for t in pq.tables}, 1)
    run = compile_plan(plan, tables)
    hlo = run._jfn.lower(*run._flat).compile().as_text()
    found = re.findall(r'= .*? ([a-z][\w-]*)\(.*op_name="jit\(q3\)/join_pk/([^/"]*)', hlo)
    assert {path for _, path in found} == {"probe_merge"}, found
    ops = {op for op, _ in found}
    assert "sort" in ops and "while" not in ops, sorted(ops)

