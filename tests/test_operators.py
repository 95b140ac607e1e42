"""The dense group-by against a float64 numpy group-by, on both of its
paths: compare-and-reduce up to ``DENSE_COMPARE_MAX_GROUPS`` groups, the
blocked scatter above it."""

import jax
import numpy as np
import pytest

from repro.relational import operators as ops

GROUPS = [1, 6, 7, ops.DENSE_COMPARE_MAX_GROUPS, ops.DENSE_COMPARE_MAX_GROUPS + 1]
# One row count that ends in a partial block, one of about 1M rows.
ROWS = [3 * ops.SUM_BLOCK + 917, 1 << 20]


def _numpy_groupby(gid, num_groups, cols, valid):
    g = gid[valid]
    sums = {
        name: np.bincount(g, weights=col[valid].astype(np.float64), minlength=num_groups)
        for name, col in cols.items()
    }
    return sums, np.bincount(g, minlength=num_groups)


@pytest.mark.parametrize("masked", ["some", "all"])
@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("num_groups", GROUPS)
def test_groupby_dense_matches_numpy(num_groups, rows, masked):
    rng = np.random.default_rng(num_groups * 7919 + rows)
    gid = rng.integers(0, num_groups, rows).astype(np.int32)
    valid = rng.random(rows) < 0.7 if masked == "some" else np.zeros(rows, bool)
    cols = {
        "price": rng.integers(90_000, 10_500_000, rows).astype(np.int32),
        "disc": (rng.integers(0, 11, rows) / 100.0).astype(np.float32),
    }
    kinds = {"price": "sum", "n": "count", "disc": "sum"}

    def run(g, c, v):
        aggs = {name: (c.get(name, g), kind) for name, kind in kinds.items()}
        return ops.groupby_dense(g, num_groups, aggs, v)

    got = jax.jit(run)(gid, cols, valid)
    want_sums, want_count = _numpy_groupby(gid, num_groups, cols, valid)

    assert set(got) == set(kinds)
    assert got["n"].dtype == np.int32
    np.testing.assert_array_equal(np.asarray(got["n"]), want_count)
    for name, want in want_sums.items():
        assert got[name].dtype == np.float32
        np.testing.assert_allclose(np.asarray(got[name], np.float64), want, rtol=1e-5)
