"""Plain numpy reference answers of the TPC-H templates the traffic uses.

Same semantics as TPC-H Q1, Q3, Q6, Q17 and Q18 at the default substitution
parameters, over the tables of :mod:`tpch_data`, written with whole-column
numpy (``bincount``, lookup tables, ``lexsort``) instead of row loops, and
independent of the system under test.

Every function takes ``fl``, a rounding applied after each floating-point
step.  ``exact`` (float64) gives the reference; ``bf16`` stores every value
in bfloat16 with wide accumulation, as a TPU reduces bfloat16 operands: that
is the control, the step below the float32 the configuration states.

Answers:

* Q1 - ``{field: array[6]}``, one cell per (returnflag, linestatus);
* Q6, Q17 - a float;
* Q3, Q18 - ``{field: array}`` of EVERY qualifying row in rank order, so a
  comparison can tell a near-tie at the top-k boundary from a wrong row.

A field held as an integer must match exactly; a float field within a
relative limit.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from tpch_data import N_LINESTATUS, N_RETURNFLAGS, days

TOPK = {
    "q3": dict(key="o_orderkey", by="revenue", k=10),
    "q18": dict(key="o_orderkey", by="o_totalprice", k=100),
}


def exact(x):
    return np.asarray(x, np.float64)


def bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


def _lookup(keys: np.ndarray, mask: np.ndarray, size: int) -> np.ndarray:
    """Boolean table over ``[0, size)``: True at the keys where ``mask``."""
    out = np.zeros(size, bool)
    out[keys[mask]] = True
    return out


def q1(t, fl=exact) -> dict[str, np.ndarray]:
    li = t["lineitem"]
    m = li["l_shipdate"] <= days(1998, 12, 1) - 90
    ngroups = N_RETURNFLAGS * N_LINESTATUS
    gid = (li["l_returnflag"] * N_LINESTATUS + li["l_linestatus"])[m]
    price = fl(li["l_extendedprice"][m])
    disc = fl(li["l_discount"][m] / 100.0)
    tax = fl(li["l_tax"][m] / 100.0)
    disc_price = fl(price * fl(1.0 - disc))
    charge = fl(disc_price * fl(1.0 + tax))

    def total(w):
        return fl(np.bincount(gid, weights=w, minlength=ngroups))

    count = np.bincount(gid, minlength=ngroups).astype(np.int64)
    out = {
        "sum_qty": total(li["l_quantity"][m].astype(np.float64)),
        "sum_base_price": total(price),
        "sum_disc_price": total(disc_price),
        "sum_charge": total(charge),
        "sum_disc": total(disc),
        "count_order": count,
    }
    n = np.maximum(count, 1)
    out["avg_qty"] = fl(out["sum_qty"] / n)
    out["avg_price"] = fl(out["sum_base_price"] / n)
    out["avg_disc"] = fl(out["sum_disc"] / n)
    return out


def q6(t, fl=exact) -> float:
    li = t["lineitem"]
    d = li["l_discount"]
    m = (
        (li["l_shipdate"] >= days(1994, 1, 1)) & (li["l_shipdate"] < days(1995, 1, 1))
        & (d >= 5) & (d <= 7) & (li["l_quantity"] < 24)
    )
    rev = fl(fl(li["l_extendedprice"][m]) * fl(d[m] / 100.0))
    return float(fl(rev.sum()))


def q17(t, fl=exact) -> float:
    li, pt = t["lineitem"], t["part"]
    npart = int(pt["p_partkey"].max()) + 1
    chosen = _lookup(pt["p_partkey"], (pt["p_brand"] == 12) & (pt["p_container"] == 2), npart)
    m = chosen[li["l_partkey"]]
    pk, qty = li["l_partkey"][m], li["l_quantity"][m]
    cnt = np.bincount(pk, minlength=npart)
    avg = fl(fl(np.bincount(pk, weights=qty, minlength=npart)) / np.maximum(cnt, 1))
    small = fl(qty) < fl(0.2 * avg[pk])
    return float(fl(fl(li["l_extendedprice"][m][small]).sum()) / 7.0)


def q3(t, fl=exact) -> dict[str, np.ndarray]:
    cu, od, li = t["customer"], t["orders"], t["lineitem"]
    cutoff = days(1995, 3, 15)
    ncust = int(cu["c_custkey"].max()) + 1
    norder = int(od["o_orderkey"].max()) + 1
    good_cust = _lookup(cu["c_custkey"], cu["c_mktsegment"] == 1, ncust)
    good_order = _lookup(
        od["o_orderkey"], (od["o_orderdate"] < cutoff) & good_cust[od["o_custkey"]], norder
    )
    m = (li["l_shipdate"] > cutoff) & good_order[li["l_orderkey"]]
    ok = li["l_orderkey"][m]
    rev = fl(fl(li["l_extendedprice"][m]) * fl((100 - li["l_discount"][m]) / 100.0))
    revenue = fl(np.bincount(ok, weights=rev, minlength=norder))
    keys = np.flatnonzero(np.bincount(ok, minlength=norder))
    order = np.lexsort((keys, -revenue[keys]))
    return {"o_orderkey": keys[order].astype(np.int64), "revenue": revenue[keys][order]}


def q18(t, fl=exact) -> dict[str, np.ndarray]:
    li, od, cu = t["lineitem"], t["orders"], t["customer"]
    norder = int(od["o_orderkey"].max()) + 1
    ncust = int(cu["c_custkey"].max()) + 1
    sums = np.bincount(li["l_orderkey"], weights=li["l_quantity"], minlength=norder)
    seg = np.full(ncust, -1, np.int64)
    seg[cu["c_custkey"]] = cu["c_mktsegment"]
    qty = sums[od["o_orderkey"]]
    m = (qty > 300) & (seg[od["o_custkey"]] >= 0)
    price = od["o_totalprice"][m].astype(np.int64)
    okey = od["o_orderkey"][m].astype(np.int64)
    # the rank key passes through float, as a TPU top-k sorts it
    order = np.lexsort((okey, -fl(price)))
    return {
        "o_orderkey": okey[order],
        "o_custkey": od["o_custkey"][m][order].astype(np.int64),
        "c_mktsegment": seg[od["o_custkey"][m]][order],
        "o_orderdate": od["o_orderdate"][m][order].astype(np.int64),
        "o_totalprice": price[order],
        "sum_qty": np.rint(fl(qty[m][order])).astype(np.int64),
    }


TEMPLATES = {"q1": q1, "q3": q3, "q6": q6, "q17": q17, "q18": q18}
TABLES = {
    "q1": ("lineitem",),
    "q3": ("customer", "orders", "lineitem"),
    "q6": ("lineitem",),
    "q17": ("lineitem", "part"),
    "q18": ("lineitem", "orders", "customer"),
}


def expected(name: str, tables) -> dict | float:
    """The exact answer of template ``name`` over ``tables``."""
    return TEMPLATES[name](tables, exact)


def control(name: str, tables) -> dict | float:
    """The answer as a program computing in bfloat16 would return it: the
    reference in bfloat16, cut to the template's top k."""
    ans = TEMPLATES[name](tables, bf16)
    if name in TOPK:
        return {f: v[: TOPK[name]["k"]] for f, v in ans.items()}
    return ans
