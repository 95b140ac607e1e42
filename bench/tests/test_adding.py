"""A cell, a configuration, a traffic mix and a per-layer metric added as
files alone resolve and run, and the readers refuse unknown keys."""

import json
import shutil
import types

import jax
import pytest

import cell as cells
import run
from conftest import BENCH, cell_of


@pytest.fixture
def copy(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__", ".jax_cache", "tests"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return root


def add_cell(root):
    cfg = json.loads((root / "configs" / "tpch-sf1-1chip.json").read_text())
    cfg["scale_factor"] = 0.01
    cfg["reduced"] = cfg["reduced"] + ["scale_factor"]
    (root / "configs" / "tpch-tiny.json").write_text(json.dumps(cfg))
    mix = {"templates": ["q6", "q18"], "streams": 3, "trace_rounds": 2, "note": "test mix"}
    (root / "traffic" / "mixed.json").write_text(json.dumps(mix))
    (root / "metrics" / "queries_traced.py").write_text(
        "def read(view):\n    return float(view.queries) or None\n"
    )
    bench = json.loads((root.parent / "BENCHMARK.json").read_text())
    p50 = next(m for m in bench["end_to_end"] if m["name"] == "ttfr_p50_s")
    p50["workloads"].append("tpch-tiny.mixed")
    bench["workloads"].append({"name": "tpch-tiny.mixed", "config": "tpch-tiny",
                               "traffic": "mixed", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queries_traced", "unit": "queries", "better": "higher",
                               "source": "device_trace", "layer": "test", "moves": "qps",
                               "workloads": ["tpch-tiny.mixed"]})
    (root.parent / "BENCHMARK.json").write_text(json.dumps(bench))


def test_cell_added_as_files_resolves_and_runs(copy):
    add_cell(copy)
    c = cells.resolve("tpch-tiny.mixed", root=copy)
    assert c.config["scale_factor"] == 0.01 and c.traffic["templates"] == ["q6", "q18"]
    assert [m["name"] for m in c.end_to_end] == ["ttfr_p50_s", "setup_s"]
    assert [m["name"] for m in c.per_layer] == ["queries_traced"]
    read = cells.load_metric("queries_traced", c.root)
    assert read(types.SimpleNamespace(queries=6)) == 6.0
    res = run.run_cell(c, 7, 0.5, False, jax.devices())
    assert res["correct"] and res["attempted"] % 3 == 0
    assert set(res["metrics"]) == {"ttfr_p50_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_listed_cells_resolve():
    listed = json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]
    for wl in (w["name"] for w in listed):
        c = cells.resolve(wl)
        assert c.chips == c.config["chips"]
        reported = {m["name"] for m in c.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2 and c.per_layer
        for m in c.per_layer:
            assert callable(cells.load_metric(m["name"]))


@pytest.mark.parametrize("kind, name", [("configs", "tpch-sf1-1chip"), ("traffic", "join")])
def test_unknown_key_is_refused(copy, kind, name):
    path = copy / kind / f"{name}.json"
    obj = json.loads(path.read_text())
    obj["stream"] = 3  # a typo of a key
    path.write_text(json.dumps(obj))
    load = cells.load_config if kind == "configs" else cells.load_traffic
    with pytest.raises(ValueError, match="unknown keys"):
        load(name, copy)


def test_template_without_reference_is_refused(copy):
    path = copy / "traffic" / "join.json"
    obj = json.loads(path.read_text())
    obj["templates"] = ["q3", "q99"]
    path.write_text(json.dumps(obj))
    with pytest.raises(ValueError, match="no reference"):
        cells.load_traffic("join", copy)


def test_tiny_join_cell_runs_correct_on_cpu():
    res = run.run_cell(cell_of("tpch-sf1-1chip", "q3-q18", 1), 2**31 + 11, 0.5, False,
                       jax.devices())
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["wrong"]["value"] == 0


def test_benchmark_json_metrics_fit_its_cells():
    """Every cell a metric lists is listed; each cell's end-to-end metrics
    are computed by ``run.end_to_end`` and are distinct quantities; each of
    its per-layer metrics moves an end-to-end metric the cell reports."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    listed = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", listed)) <= listed, m["name"]
    w = run.Window(answers=[], latencies=[0.001 * i for i in range(1, 101)], attempted=100,
                   failed=0, close_s=7.0, traced_rounds=0, traced={}, compiles=0)
    for name in sorted(listed):
        c = cells.resolve(name)
        values = {m["name"]: run.end_to_end(m["name"], w, setup_s=11.0) for m in c.end_to_end}
        same = [(a, b) for a in values for b in values if a < b and values[a] == values[b]]
        assert not same, f"{name} reports one quantity twice: {same}"
        for m in c.per_layer:
            assert m["moves"] in values, (name, m["name"])
