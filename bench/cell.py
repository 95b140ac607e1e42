"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration and a traffic mix; each lives in a JSON file of
its own (``configs/<config>.json``, ``traffic/<traffic>.json``), and each
per-layer metric in ``metrics/<name>.py`` (or ``metrics/<reader>.py`` for a
name ``<reader>.<part>``).  Adding a cell, a mix or a metric
is adding files: nothing here names one.  The readers refuse unknown and
missing keys, so a typo fails instead of quietly changing what runs.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent

CONFIG_KEYS = {
    "source": str,
    "deployment": str,
    "scale_factor": (int, float),
    "chips": int,
    "mesh": dict,
    "tables": list,
    "column_encoding": str,
    "substitution_parameters": str,
    "refresh_functions": str,
    "guarantee": str,
    "limits": dict,
    "reduced": list,
    "assumed": dict,
}
MESH_KEYS = {"num_shards": int, "num_pods": int}
LIMIT_KEYS = {"rel_err": (int, float), "wrong": int}
TRAFFIC_KEYS = {"templates": list, "streams": int, "trace_rounds": int, "note": str}


def _strict(obj, keys: dict, what: str) -> dict:
    if not isinstance(obj, dict):
        raise ValueError(f"{what}: expected an object")
    unknown, missing = set(obj) - set(keys), set(keys) - set(obj)
    if unknown or missing:
        raise ValueError(f"{what}: unknown keys {sorted(unknown)}, missing {sorted(missing)}")
    for k, kind in keys.items():
        if not isinstance(obj[k], kind) or isinstance(obj[k], bool):
            raise ValueError(f"{what}: {k!r} must be {kind}, got {obj[k]!r}")
    return obj


def load_config(name: str, root: Path = HERE) -> dict:
    path = root / "configs" / f"{name}.json"
    cfg = _strict(json.loads(path.read_text()), CONFIG_KEYS, str(path))
    _strict(cfg["mesh"], MESH_KEYS, f"{path}: mesh")
    _strict(cfg["limits"], LIMIT_KEYS, f"{path}: limits")
    for key in cfg["reduced"]:
        if key not in cfg:
            raise ValueError(f"{path}: reduced names {key!r}, which the file lacks")
    return cfg


def load_traffic(name: str, root: Path = HERE) -> dict:
    from reference import TEMPLATES

    path = root / "traffic" / f"{name}.json"
    mix = _strict(json.loads(path.read_text()), TRAFFIC_KEYS, str(path))
    bad = [t for t in mix["templates"] if t not in TEMPLATES]
    if bad or not mix["templates"]:
        raise ValueError(f"{path}: templates {bad} have no reference (known: {sorted(TEMPLATES)})")
    if mix["streams"] < 1 or mix["trace_rounds"] < 1:
        raise ValueError(f"{path}: streams and trace_rounds must be >= 1")
    return mix


def load_metric(name: str, root: Path = HERE):
    """The ``read(run)`` function of ``metrics/<name>.py``.  A name
    ``<reader>.<part>`` with no file of its own is read by
    ``metrics/<reader>.py``: one quantity, listed once for each end-to-end
    metric it moves, as ``fetch_ms_per_query.host`` in the host-bound cells."""
    path = root / "metrics" / f"{name}.py"
    if not path.exists():
        path = root / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Cell:
    name: str
    root: Path  # the bench directory its files came from
    config: dict
    traffic: dict
    chips: int
    end_to_end: list[dict]  # the cell's end-to-end metrics
    per_layer: list[dict]  # the cell's per-layer metrics


def _covers(metric: dict, cell: str, reported: set[str] | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def resolve(workload: str, root: Path = HERE) -> Cell:
    """The cell named ``workload`` in ``<root>/../BENCHMARK.json``."""
    bench = json.loads((root.parent / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[workload]
    return make(workload, w["config"], w["traffic"], w["chips"], bench, root)


def make(name: str, config: str, traffic: str, chips: int, bench: dict, root: Path = HERE) -> Cell:
    """A cell of ``configs/<config>.json`` under ``traffic/<traffic>.json``,
    with the metrics of ``bench`` (a ``BENCHMARK.json`` object) that cover it."""
    cfg = load_config(config, root)
    if cfg["chips"] != chips:
        raise ValueError(f"{name}: config {config} is for {cfg['chips']} chips")
    e2e = [m for m in bench["end_to_end"] if _covers(m, name)]
    reported = {m["name"] for m in e2e}
    return Cell(
        name=name,
        root=root,
        config=cfg,
        traffic=load_traffic(traffic, root),
        chips=chips,
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"] if _covers(m, name, reported)],
    )
