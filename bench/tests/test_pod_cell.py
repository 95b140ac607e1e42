"""The two-level cell: ``tpch-sf4-pod2x2.join`` resolves from
``BENCHMARK.json``, its configuration loads under the strict keys, and the
cell runs correct at a tiny scale on four CPU devices as a ``(2, 2)`` pod
mesh: Q3, Q17 and Q18 served by ``QueryServeEngine`` and equal to the
reference, with no exchange dropping a row (a drop raises in the executor,
so the round would fail)."""

import os
import subprocess
import sys
import textwrap

import pytest

import cell as cells
import readings
from conftest import BENCH, cell_of

CELL = "tpch-sf4-pod2x2.join"


def test_pod_cell_resolves_and_its_config_loads():
    c = cells.resolve(CELL)
    assert c.chips == 4 and c.config["mesh"] == {"num_shards": 4, "num_pods": 2}
    assert c.config == cells.load_config("tpch-sf4-pod2x2")
    assert c.traffic["templates"] == ["q3", "q17", "q18"] and c.traffic["streams"] == 2
    assert [m["name"] for m in c.end_to_end] == ["ttfr_p50_s", "setup_s"]
    assert [m["name"] for m in c.per_layer] == ["collective_ms_per_query"]
    flat = cells.load_config("tpch-sf4-4chip")
    for key in ("scale_factor", "tables", "column_encoding", "substitution_parameters",
                "refresh_functions", "guarantee", "limits", "reduced"):
        assert c.config[key] == flat[key], key


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_control_fails_the_pod_cell_limits(seed):
    """The reference in bfloat16, in the system's place, reads incorrect on
    the join mix, Q17 included, by the configuration's limits."""
    c = cell_of("tpch-sf4-pod2x2", "join", 4, 0.02)
    got = readings.control_reading(c, seed)
    limits = c.config["limits"]
    assert got["rel_err"] > limits["rel_err"] or got["wrong"] > limits["wrong"], got


RUN = """
import sys
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import jax
import cell
import run
from conftest import tiny
c = tiny(cell.resolve({name!r}), 0.01)
res = run.run_cell(c, 2**31 + 11, 0.5, False, jax.devices())
print("RESULT", res["correct"], res["failed"], res["attempted"], res["checks"])
"""


def test_pod_cell_runs_correct_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(RUN.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                                      tests=str(BENCH / "tests"), name=CELL))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=BENCH / "tests",
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(x for x in out.stdout.splitlines() if x.startswith("RESULT"))
    correct, failed, attempted = line.split()[1:4]
    assert (correct, failed) == ("True", "0"), out.stdout[-2000:]
    assert int(attempted) > 0
