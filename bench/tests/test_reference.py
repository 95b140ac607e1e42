"""The benchmark's data and reference against the system's generator and
its loop oracle: bit-identical tables, and every field of every answer of
the five templates equal, at SF 0.01 on two seeds."""

import numpy as np
import pytest

import compare
import reference
import tpch_data
from repro.relational import datagen, oracle
from repro.relational.table import from_numpy

SEEDS = (0, 2**31 + 5)


@pytest.fixture(scope="module", params=SEEDS)
def tables(request):
    ours = tpch_data.generate(0.01, request.param)
    theirs = datagen.gen_all(0.01, seed=request.param)
    return ours, theirs


def test_tables_bit_identical(tables):
    ours, theirs = tables
    assert set(ours) == set(theirs)
    for t, cols in ours.items():
        want = {k: np.asarray(v) for k, v in theirs[t].columns.items()}
        assert set(cols) == set(want), t
        for k, v in cols.items():
            assert v.dtype == want[k].dtype and np.array_equal(v, want[k]), (t, k)


def _close(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)


@pytest.mark.parametrize("name", sorted(reference.TEMPLATES))
def test_reference_equals_oracle(tables, name):
    ours, theirs = tables
    got = reference.expected(name, ours)
    want = oracle.expected(name, theirs)
    if name in reference.TOPK:
        k = reference.TOPK[name]["k"]
        got = {f: v[:k] for f, v in got.items()}
        want = want if isinstance(want, dict) else {}
        assert set(got) == set(want)
        for f in want:
            _close(got[f], want[f])
    elif isinstance(want, dict):
        assert set(want) <= set(got)
        for f in want:
            _close(got[f], want[f])
        cnt = np.maximum(want["count_order"], 1)
        _close(got["avg_qty"], want["sum_qty"] / cnt)
        _close(got["avg_price"], want["sum_base_price"] / cnt)
        _close(got["avg_disc"], want["sum_disc"] / cnt)
    else:
        _close(got, want)


@pytest.mark.parametrize("name", sorted(reference.TEMPLATES))
def test_system_answer_passes_and_control_fails(tables, name):
    """The system's own answer at this size passes the comparison, and the
    bfloat16 control reads at least 10x above the float32 system."""
    from repro.relational.planner import tpch

    ours, _ = tables
    tabs = {t: from_numpy(c) for t, c in ours.items()}
    pq = tpch.ALL_QUERIES[name]()
    got = tpch.run_query(pq, tabs)
    want = reference.expected(name, ours)
    system, control = compare.Verdict(), compare.Verdict()
    system.answer(name, got, want)
    control.answer(name, reference.control(name, ours), want)
    assert system.wrong == 0 and system.rel_err < 1e-5
    assert control.wrong > 0 or control.rel_err > 10 * max(system.rel_err, 1e-6)
