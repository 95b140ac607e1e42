"""A run whose timed path is broken underneath reads ``correct`` false.

Each test skips the harness's look for a chip and drives the rest of a run
on the CPU at SF 0.01, with one fault planted in the system:

* half of every table's rows left out (marked invalid where the tables are
  placed on the mesh), so sums and means are taken over the rest;
* the exchange between chips left out (shuffles and broadcasts return the
  local rows), on four virtual CPU devices in a child process;
* an answer altered where it is produced (one float value of every fetched
  result moved by a few limits).

A read-only cell has no state that a step could leave unchanged, so that
fault of a training cell does not apply.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import run
from conftest import BENCH, cell_of
from repro.relational.planner import executor


def half_rows(monkeypatch):
    place = executor._place

    def halved(table, *a, **kw):
        cols, valid = place(table, *a, **kw)
        return cols, valid & (jax.numpy.arange(valid.shape[0]) % 2 == 0)

    monkeypatch.setattr(executor, "_place", halved)


def altered_answer(monkeypatch):
    collect = executor.CompiledRunner.collect

    def altered(self, out, t_dispatch=None):
        result, qt = collect(self, out, t_dispatch)
        name = next(k for k in sorted(result) if np.asarray(result[k]).dtype.kind == "f")
        v = np.array(result[name], copy=True)
        v.reshape(-1)[0] *= 1.001
        return {**result, name: v}, qt

    monkeypatch.setattr(executor.CompiledRunner, "collect", altered)


@pytest.mark.parametrize("fault", [half_rows, altered_answer])
@pytest.mark.parametrize("traffic", ["q3-q18", "scan"])
def test_fault_reads_incorrect(monkeypatch, fault, traffic):
    fault(monkeypatch)
    res = run.run_cell(cell_of("tpch-sf1-1chip", traffic, 1), 5, 0.3, False, jax.devices())
    assert not res["correct"], res["checks"]


def test_sound_run_reads_correct():
    res = run.run_cell(cell_of("tpch-sf1-1chip", "scan", 1), 5, 0.3, False, jax.devices())
    assert res["correct"], res["checks"]


FOUR = """
import sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax
import run
from conftest import cell_of
from repro.relational.planner import executor
if {broken}:
    executor._exchange_by_key = lambda mux, t, key, columns, route_keys=None: (
        executor.Table({{c: t[c] for c in columns}}, t.valid), jax.numpy.int32(0))
    executor._broadcast_table = lambda mux, t, columns: (
        executor.Table({{c: t[c] for c in columns}}, t.valid), jax.numpy.int32(0))
res = run.run_cell(cell_of("tpch-sf4-4chip", "q3-q18", 4, 0.02), 5, 0.3, False, jax.devices())
print("CORRECT", res["correct"], res["checks"])
"""


@pytest.mark.parametrize("broken", [False, True])
def test_exchange_left_out_reads_incorrect(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(FOUR.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                                       broken=broken))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=BENCH / "tests",
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"CORRECT {not broken}" in out.stdout, out.stdout[-2000:]
