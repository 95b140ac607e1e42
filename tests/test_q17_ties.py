"""Q17 is exact on ties: ``l_quantity < 0.2 * avg(l_quantity)`` is decided
in integers, so a row whose quantity is exactly a fifth of its part's
average is never counted, and one a hair below always is.

The data is TPC-H at a tiny scale with a tenth of the parts moved to Q17's
brand and container, and their lineitems' quantities rewritten so that most
of those parts hold rows exactly at the boundary (``5 * q * cnt == sum``)
and the rest one unit to either side.  Q17 goes through
``QueryServeEngine`` on one device, a flat 4-way mesh and a ``(2, 2)`` pod
mesh (the multi-device meshes in a subprocess with four CPU devices), and
is held to the float64 reference of ``bench/reference.py`` and to
``relational/oracle.py``: a single flipped row would move the answer by
more than half the cheapest boundary row's price.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)
BENCH = os.path.join(ROOT, "bench")
MESHES = {"one": (1, 1), "flat4": (4, 1), "pod2x2": (4, 2)}
BRAND, CONTAINER = 12, 2  # Q17's defaults


def tie_dense_data(seed: int, sf: float = 0.01):
    """TPC-H ``lineitem`` and ``part`` columns with Q17's boundary crowded.

    Returns ``(data, boundary)``: the numpy tables and a mask of the
    lineitem rows at or one unit beside their part's boundary."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import tpch_data

    data = tpch_data.generate(sf, seed, ["lineitem", "part"])
    rng = np.random.default_rng(seed)
    pt, li = data["part"], data["lineitem"]
    chosen = rng.random(pt["p_partkey"].shape[0]) < 0.1
    pt["p_brand"][chosen] = BRAND
    pt["p_container"][chosen] = CONTAINER
    qty = li["l_quantity"]
    boundary = np.zeros(qty.shape[0], bool)
    rows_of = np.argsort(li["l_partkey"], kind="stable")
    starts = np.searchsorted(li["l_partkey"][rows_of], np.arange(chosen.shape[0] + 1))
    for pk in np.flatnonzero(chosen):
        rows = rows_of[starts[pk]:starts[pk + 1]]
        cnt = rows.shape[0]
        if cnt < 2:
            continue
        t = int(rng.integers(1, 7))  # the quantity at the boundary
        k = max(1, cnt // 3)  # rows placed there
        total = 5 * t * cnt + int(rng.choice([-1, 0, 0, 0, 1]))
        rest, m = total - k * t, cnt - k
        if not m <= rest <= 50 * m:
            continue
        fill = np.full(m, rest // m, np.int32)
        fill[: rest % m] += 1
        qty[rows[:k]] = t
        qty[rows[k:]] = rng.permutation(fill)
        boundary[rows[:k]] = True
    return data, boundary


def check_q17_on_ties(num_shards: int, num_pods: int, seed: int = 17) -> None:
    """Serve Q17 over tie-dense tables on the given mesh and compare."""
    for path in (BENCH, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import reference

    from repro.relational import oracle
    from repro.relational.context import ExecutionContext
    from repro.relational.planner import tpch
    from repro.relational.table import from_numpy
    from repro.serve import QueryRequest, QueryServeEngine

    data, boundary = tie_dense_data(seed)
    li, pt = data["lineitem"], data["part"]
    q = li["l_quantity"].astype(np.int64)
    pk = li["l_partkey"]
    chosen = np.isin(pk, pt["p_partkey"][(pt["p_brand"] == BRAND)
                                         & (pt["p_container"] == CONTAINER)])
    sums = np.bincount(pk[chosen], weights=q[chosen], minlength=pt["p_partkey"].shape[0])
    cnts = np.bincount(pk[chosen], minlength=pt["p_partkey"].shape[0])
    ties = chosen & (5 * q * cnts[pk] == sums[pk])
    assert ties.sum() >= 100, ties.sum()  # the boundary really is crowded
    assert (chosen & (5 * q * cnts[pk] == sums[pk] - 1)).any()
    assert (chosen & (5 * q * cnts[pk] == sums[pk] + 1)).any()

    tables = {t: from_numpy(cols) for t, cols in data.items()}
    pq = tpch.q17()
    engine = QueryServeEngine(
        tables, ExecutionContext(num_shards=num_shards, num_pods=num_pods),
        num_slots=1, templates=[pq],
    )
    (req,) = engine.serve([QueryRequest(tenant="t", query=pq)])
    got = float(req.result)
    want = reference.q17(data)
    want_oracle = oracle.q17_oracle(tables["lineitem"], tables["part"])
    assert want == pytest.approx(want_oracle, rel=1e-12)
    one_row = li["l_extendedprice"][boundary].min() / 7.0
    assert abs(got - want) < 0.5 * one_row, (got, want, one_row)
    assert got == pytest.approx(want, rel=1e-5)
    for edge in req.trace.edges:
        assert int(np.sum(edge.hist)) > 0


@pytest.mark.parametrize("mesh", list(MESHES))
def test_q17_exact_on_ties(mesh):
    shards, pods = MESHES[mesh]
    if shards == 1:
        check_q17_on_ties(shards, pods)
        return
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {TESTS!r})
        import test_q17_ties
        test_q17_ties.check_q17_on_ties({shards}, {pods})
        print("EXACT")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0 and "EXACT" in out.stdout, out.stdout[-2000:] + out.stderr[-4000:]


def _float_leaves(expr, float_cols):
    """The parts of ``expr`` that compute in float: columns in
    ``float_cols``, float literals, true division and casts to f32.  An i32
    cast of a column reads that column's value as an integer (the group
    sums are exact integer totals) and computes nothing in float."""
    from repro.relational.planner import logical as L

    if isinstance(expr, L.Col):
        return [expr.name] if expr.name in float_cols else []
    if isinstance(expr, L.Lit):
        return [expr.value] if isinstance(expr.value, float) else []
    if isinstance(expr, L.Cast):
        if expr.dtype == "i32" and isinstance(expr.child, L.Col):
            return []
        own = [expr.render()] if expr.dtype == "f32" else []
        return own + _float_leaves(expr.child, float_cols)
    if isinstance(expr, L.Bin):
        own = [expr.render()] if expr.op == "/" else []
        return own + _float_leaves(expr.lhs, float_cols) + _float_leaves(expr.rhs, float_cols)
    if isinstance(expr, L.Where):
        return sum((_float_leaves(e, float_cols) for e in (expr.cond, expr.then, expr.other)), [])
    raise TypeError(type(expr))


@pytest.mark.parametrize("mesh", list(MESHES))
def test_q17_filter_operands_are_integer(mesh):
    """The physical plan decides the small-quantity test in integers: the
    per-part limit is derived from ``sum_qty`` and ``cnt`` with no float
    step, the join back carries it as an integer column, and the filter
    compares it with the integer quantity."""
    from repro.relational.planner import tpch

    shards, pods = MESHES[mesh]
    plan = tpch.q17().plan(tpch.tpch_catalog(1), shards, num_pods=pods)
    nodes, seen = [], set()

    def walk(n):
        if id(n) not in seen:
            seen.add(id(n))
            nodes.append(n)
            for c in n.children:
                walk(c)

    walk(plan.root)
    (small,) = [n for n in nodes if n.kind == "filter"
                and "l_quantity" in n.info["pred"].columns()]
    (back,) = [n for n in nodes if n.kind == "join" and n.info["payload"]]
    (limit,) = [n for n in nodes if n.kind == "project"
                and any(name in back.info["payload"] for name, _ in n.info["derived"])]
    assert back.info["payload"] == ("qty_limit",)
    assert not (set(back.info["payload"]) & back.float_cols)
    for name, expr in limit.info["derived"]:
        assert expr.columns() == {"sum_qty", "cnt"}
        assert _float_leaves(expr, limit.children[0].float_cols) == [], name
    child = small.children[0]
    assert small.info["pred"].columns() == {"l_quantity", "qty_limit"}
    assert _float_leaves(small.info["pred"], child.float_cols) == []
