"""The trace reduction on a recorded trace: two rounds of
``tpch-sf1-1chip.scan`` on one TPU v5e, cut by ``trace_reduce.py`` to the
device ops and the host events of 1 ms or more, and on hand-made events."""

import gzip
from pathlib import Path

import numpy as np
import pytest

import trace_reduce as tr
from conftest import BENCH

RECORDED = Path(__file__).parent / "data" / "scan_1chip.pbtxt.gz"


@pytest.fixture(scope="module")
def scan():
    return tr.load(RECORDED)


def brute_busy_ns(events, lo, hi):
    """Busy ns by marking every covered microsecond."""
    covered = np.zeros((hi - lo) // 1000 + 1, bool)
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if a < b:
            covered[(a - lo) // 1000:(b - lo) // 1000] = True
    return covered.sum() * 1000


def test_recorded_trace_shape(scan):
    assert list(scan.devices) == [0]
    assert len(scan.rounds) == 2
    assert {s.name for s in scan.spans} == {"bench.round", "bench.finalize"}
    modules = {e.module for e in scan.devices[0]}
    assert all(m.startswith("jit_") for m in modules) and len(modules) >= 2


def test_busy_and_window(scan):
    lo, hi = scan.window
    assert (lo, hi) == (scan.rounds[0].start, scan.rounds[1].end)
    busy = scan.busy_ns(0)
    assert abs(busy - brute_busy_ns(scan.devices[0], lo, hi)) <= 2000 * len(scan.devices[0])
    assert 0.9 < busy / (hi - lo) < 1.0
    gaps = scan.gaps(0)
    assert sum(b - a for a, b in gaps) + busy == hi - lo


def test_breakdown(scan):
    program = frozenset(p.name for p in (BENCH.parent / "src").rglob("*.py"))
    b = tr.breakdown(scan, program)
    secs = [s for _, s in b["device_ops"]]
    assert len(secs) == 10 and secs == sorted(secs, reverse=True)
    # Q1's six blocked segment sums take almost all of a scan round
    assert sum(secs[:6]) / scan.busy_s() > 0.9
    assert all(label.startswith("bench.") for label, _ in b["idle_gaps"])
    assert any("fetch" in label for label, _ in b["idle_gaps"])


def test_metrics_read_the_recorded_trace(scan):
    import types

    import cell

    view = types.SimpleNamespace(trace=scan, queries=4, traced={"q1": 2, "q6": 2},
                                 peaks={"hbm_bytes_per_s": 819e9}, pack_calls={})
    idle = cell.load_metric("device_idle_share")(view)
    assert idle == pytest.approx(1 - scan.busy_s() / scan.window_s())
    ms = cell.load_metric("device_ms_per_query")(view)
    assert ms == pytest.approx(scan.busy_s() * 1e3 / 4)
    assert cell.load_metric("collective_ms_per_query")(view) is None
    assert cell.load_metric("pack_roofline")(view) is None


def test_self_time_and_modules():
    E = tr.Event
    ops = [E(0, 100, "while"), E(10, 40, "fusion.1"), E(50, 60, "fusion.2"), E(120, 130, "sort")]
    mods = [E(0, 110, "jit_a(1)"), E(115, 140, "jit_b(2)")]
    tagged = tr._in_modules(ops, mods)
    assert [e.module for e in tagged] == ["jit_a(1)"] * 3 + ["jit_b(2)"]
    t = tr.Trace({0: tagged}, [E(0, 140, "bench.round")], [E(100, 125, "$x.py:1 f")])
    assert t.op_seconds() == pytest.approx({
        ("jit_a(1)", "while"): 60e-9, ("jit_a(1)", "fusion.1"): 30e-9,
        ("jit_a(1)", "fusion.2"): 10e-9, ("jit_b(2)", "sort"): 10e-9})
    assert t.gaps(0) == [(100, 120), (130, 140)]
    assert t.label(110, frozenset({"x.py"})) == "bench.round/$x.py:1 f"
    assert t.label(135) == "bench.round"


def test_text_proto_round_trip(scan, tmp_path):
    path = tmp_path / "one.pbtxt.gz"
    path.write_bytes(gzip.compress(tr.text_proto(scan, 1).encode()))
    one = tr.load(path)
    assert len(one.rounds) == 1 and one.window == (scan.rounds[0].start, scan.rounds[0].end)
    lo, hi = one.window
    assert one.busy_ns(0) == sum(b - a for a, b in tr.union(scan.devices[0], lo, hi))
