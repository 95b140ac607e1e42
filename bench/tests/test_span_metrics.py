"""The metrics that read the program's own ``repro.*`` spans, on a hand-made
trace whose answers follow by arithmetic, on an older recorded trace (a
program without the spans: no readings), and on one scan round recorded on
a TPU v5e with the spans (``record_trace.py --rounds 1``)."""

import types
from pathlib import Path

import pytest

import cell
import spans
import trace_reduce as tr

DATA = Path(__file__).parent / "data"
E = tr.Event
NAMES = ("serve_ms_per_query", "fetch_ms_per_query", "transfers_per_query",
         "engine_idle_share")


def view(trace, queries):
    return types.SimpleNamespace(trace=trace, queries=queries, traced={},
                                 peaks={}, pack_calls={})


def read(name, v):
    return cell.load_metric(name)(v)


@pytest.fixture
def made():
    """Two requests in a window [0, 1000) on two chips.

    Request A: plan 100-150 (a build nested in it), dispatch 150-170, wait
    170-600, fetch 600-700 with three transfers, finalize 700-760.  Request
    B: plan 760-800 overlapped by dispatch 790-810, finalize 880-920
    overlapping fetch 900-1100, which runs past the window; one of its two
    transfers starts after the window.  Spans before the window count for
    nothing."""
    host = [
        E(-50, -10, "repro.plan"), E(-30, -20, "repro.transfer"),
        E(100, 150, "repro.plan"), E(110, 140, "repro.build"),
        E(150, 170, "repro.dispatch"), E(170, 600, "repro.wait"),
        E(600, 700, "repro.fetch"), E(610, 620, "repro.transfer"),
        E(630, 640, "repro.transfer"), E(650, 660, "repro.transfer"),
        E(700, 760, "repro.finalize"),
        E(760, 800, "repro.plan"), E(790, 810, "repro.dispatch"),
        E(880, 920, "repro.finalize"), E(900, 1100, "repro.fetch"),
        E(950, 960, "repro.transfer"), E(1010, 1020, "repro.transfer"),
        E(0, 1000, "$run.py:1 run_window"),
    ]
    devices = {
        0: [E(0, 100, "fusion.1"), E(170, 600, "fusion.2"), E(810, 880, "sort")],
        1: [E(0, 840, "fusion.1"), E(920, 1000, "fusion.2")],
    }
    return tr.Trace(devices, [E(0, 1000, "bench.round")], host)


def test_serve_ms_is_the_union_of_plan_dispatch_finalize(made):
    # 100-170, then 700-810 (finalize A, plan B, dispatch B), then 880-920
    assert read("serve_ms_per_query", view(made, 2)) == pytest.approx(220 / 1e6 / 2)


def test_fetch_ms_is_clipped_to_the_window(made):
    assert read("fetch_ms_per_query", view(made, 2)) == pytest.approx(200 / 1e6 / 2)


def test_transfers_count_those_starting_in_the_window(made):
    assert read("transfers_per_query", view(made, 2)) == 4 / 2


def test_engine_idle_share_is_idle_under_host_work(made):
    # host work: 100-170, 600-810, 880-1000.  Chip 0 idles 100-170, 600-810
    # and 880-1000, all under it; chip 1 idles 840-920, half under it.
    engine = read("engine_idle_share", view(made, 2))
    assert engine == pytest.approx((400 / 1000 + 40 / 1000) / 2)
    device = read("device_idle_share", view(made, 2))
    assert device == pytest.approx((400 / 1000 + 80 / 1000) / 2)
    assert engine <= device


def test_overlap_of_interval_lists():
    assert spans.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap([(0, 10)], [(10, 20)]) == 0
    assert spans.overlap([], [(0, 5)]) == 0


def test_no_reading_without_the_spans_or_queries(made):
    bare = tr.Trace(made.devices, made.spans, [e for e in made.host
                                              if not e.name.startswith("repro.")])
    for name in NAMES:
        assert read(name, view(bare, 2)) is None, name
    for name in NAMES[:3]:
        assert read(name, view(made, 0)) is None, name
    assert read("engine_idle_share", view(tr.Trace({}, made.spans, made.host), 2)) is None


def test_a_program_without_spans_reads_nothing():
    """Scan rounds recorded before the program had spans."""
    old = tr.load(DATA / "scan_1chip.pbtxt.gz")
    for name in NAMES:
        assert read(name, view(old, 4)) is None, name


@pytest.fixture(scope="module")
def round_on_chip():
    """One round of ``tpch-sf1-1chip.scan`` (a Q6 and a Q1) on one TPU v5e,
    cut by ``record_trace.py``: device ops and every host event but the
    Python tracer's frames."""
    return tr.load(DATA / "scan_round_spans.pbtxt.gz")


def test_recorded_round_reads(round_on_chip):
    t = round_on_chip
    assert len(t.rounds) == 1
    programs = {e.module.split("(")[0] for e in t.devices[0]}
    assert {"jit_q1", "jit_q6"} <= programs
    # the rest are Q1's finalize: eager jnp ops, inside repro.finalize
    finalize = [e for e in t.host if e.name == "repro.finalize"]
    for e in t.devices[0]:
        if e.module.split("(")[0] not in ("jit_q1", "jit_q6"):
            assert any(f.start <= e.start < f.end for f in finalize), e
    stages = [e.name for e in t.host if e.name in
              ("repro.plan", "repro.dispatch", "repro.wait", "repro.fetch",
               "repro.finalize")]
    # both requests dispatch before either is collected
    assert stages == ["repro.plan", "repro.dispatch"] * 2 + [
        "repro.wait", "repro.fetch", "repro.finalize"] * 2
    v = view(t, 2)
    assert read("transfers_per_query", v) == 4.5  # Q1 7 reads, Q6 2
    assert read("fetch_ms_per_query", v) > 0
    assert read("serve_ms_per_query", v) > 0
    engine, device = read("engine_idle_share", v), read("device_idle_share", v)
    assert 0 < engine <= device
