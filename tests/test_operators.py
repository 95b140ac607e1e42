"""The dense group-by against a float64 numpy group-by, on both of its
paths: compare-and-reduce up to ``DENSE_COMPARE_MAX_GROUPS`` groups, the
blocked scatter above it.  The PK-FK join against a numpy dict join, and
planned templates' answers against the binary-search join it replaced."""

import jax
import jax.numpy as jnp
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


# ----------------------------------------------------------------------------
# PK-FK join.
# ----------------------------------------------------------------------------

INT32_MAX = np.iinfo(np.int32).max


def _numpy_join(bk, bv, pk, pv):
    """``match`` and the lowest valid build row of each probe's key (-1
    where there is none), by a dict."""
    first = {}
    for row, (key, ok) in enumerate(zip(bk.tolist(), bv.tolist())):
        if ok:
            first.setdefault(key, row)
    rows = np.array([first.get(k, -1) for k in pk.tolist()], np.int64)
    return (rows >= 0) & pv, rows


def _join_case(case: str, rng):
    nb, npr = 3000, 7001
    bk = (rng.permutation(nb * 4)[:nb] * 3 + 1).astype(np.int32)
    bv = np.ones(nb, bool)
    pk = bk[rng.integers(0, nb, npr)]
    pv = np.ones(npr, bool)
    if case == "duplicate_probe_keys":
        pk = bk[rng.integers(0, 20, npr)]
    elif case == "duplicate_build_keys":
        bk = rng.integers(0, 40, nb).astype(np.int32)
        bv = rng.random(nb) < 0.8
        pk = rng.integers(0, 50, npr).astype(np.int32)
        pv = rng.random(npr) < 0.9
    elif case == "absent_probe_keys":
        absent = rng.random(npr) < 0.5
        pk = np.where(absent, pk + 1, pk).astype(np.int32)  # keys are 1 mod 3
    elif case == "invalid_rows":
        bv = rng.random(nb) < 0.6
        pv = rng.random(npr) < 0.7
    elif case == "all_invalid_build":
        bv = np.zeros(nb, bool)
    elif case == "few_probes":  # a streamed morsel's shape
        pk, pv = pk[:16], pv[:16]
    elif case == "int32_max_probe":
        # invalid build rows beside a valid probe key INT32_MAX, whose own
        # build row is invalid too: nothing may match it
        bk[:10] = INT32_MAX
        bv[:200] = False
        pk[:50] = INT32_MAX
    elif case == "int32_max_build":
        # a valid build key INT32_MAX after invalid rows of the same key
        bk[:3] = INT32_MAX
        bv[:2] = False
        pk[:50] = INT32_MAX
    return bk, bv, pk, pv


@pytest.mark.parametrize("case", [
    "unique_build_keys", "duplicate_probe_keys", "duplicate_build_keys",
    "absent_probe_keys", "invalid_rows", "all_invalid_build", "few_probes",
    "int32_max_probe", "int32_max_build",
])
def test_join_pk_matches_numpy(case):
    rng = np.random.default_rng(len(case) * 131)
    bk, bv, pk, pv = _join_case(case, rng)
    idx, match = jax.jit(ops.join_pk)(bk, bv, pk, pv)
    want_match, want_rows = _numpy_join(bk, bv, pk, pv)
    assert idx.dtype == np.int32 and match.dtype == np.bool_
    np.testing.assert_array_equal(np.asarray(match), want_match)
    np.testing.assert_array_equal(np.asarray(idx)[want_match], want_rows[want_match])
    assert (np.asarray(idx) >= 0).all() and (np.asarray(idx) < len(bk)).all()
    if case == "all_invalid_build":
        assert not want_match.any()
    elif case == "int32_max_probe":
        assert not want_match[:50].any() and want_match[50:].any()
    elif case == "int32_max_build":
        assert (np.asarray(idx)[:50] == 2).all() and want_match[:50].all()


def _search_join_pk(build_keys, build_valid, probe_keys, probe_valid):
    """The binary-search probe the merge replaced: the build keys sorted
    once (invalid rows to ``INT32_MAX``), each probe searched into them."""
    skeys = jnp.where(build_valid, build_keys.astype(jnp.int32), INT32_MAX)
    order = jnp.argsort(skeys)
    sk = skeys[order]
    pos = jnp.clip(jnp.searchsorted(sk, probe_keys.astype(jnp.int32)), 0, sk.shape[0] - 1)
    return order[pos], (sk[pos] == probe_keys.astype(jnp.int32)) & probe_valid


@pytest.mark.parametrize("template", ["q3", "q17", "q18"])
def test_template_answers_equal_the_search_join(template, monkeypatch):
    """A planned template's answer is the same, bit for bit, whether its
    joins probe by the merge or by the binary search it replaced."""
    from repro.relational import datagen
    from repro.relational.planner import tpch
    from repro.relational.planner.executor import compile_plan

    tabs = datagen.gen_all(0.002)
    pq = getattr(tpch, template)()
    tables = {t: tabs[t] for t in pq.tables}
    plan = pq.plan({t: tables[t].capacity for t in pq.tables}, 1)

    merged = compile_plan(plan, tables)()
    monkeypatch.setattr(ops, "join_pk", _search_join_pk)
    searched = compile_plan(plan, tables)()
    jax.tree.map(np.testing.assert_array_equal, merged, searched)
