"""TPC-H tables from a seed, as numpy columns.

The benchmark's own copy of the system's generator (``gen_all`` with uniform
keys): the same columns, the same draws in the same order and the same
per-table seeds ``seed + 1 .. seed + 4``, so its tables are bit-identical to
the system's.  Keeping a copy here means a change to the system cannot move
the data the benchmark measures it on.

Strings are dictionary codes, money is int32 cents and dates are int32 days
since 1992-01-01.  Only the four tables the queries read are made.
"""

from __future__ import annotations

import numpy as np

CARD = {
    "customer": 150_000,
    "orders": 1_500_000,
    "lineitem": 6_000_000,
    "part": 200_000,
}
FLOORS = {"part": 64, "customer": 64, "orders": 256, "lineitem": 1024}

N_RETURNFLAGS = 3  # A, N, R
N_LINESTATUS = 2  # F, O
N_MKTSEGMENTS = 5
N_ORDERPRIORITIES = 5
N_SHIPMODES = 7
N_BRANDS = 25
N_CONTAINERS = 40
DATE_MIN_DAYS = 0  # 1992-01-01
DATE_MAX_DAYS = 2526  # about 1998-12-01


def rows(table: str, sf: float) -> int:
    """Row count of ``table`` at scale factor ``sf``."""
    return max(int(CARD[table] * sf), FLOORS[table])


def days(y: int, m: int, d: int) -> int:
    """Days from 1992-01-01 to the given date."""
    delta = np.datetime64(f"{y:04d}-{m:02d}-{d:02d}") - np.datetime64("1992-01-01")
    return int(delta / np.timedelta64(1, "D"))


def _part(sf: float, seed: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    n = rows("part", sf)
    return {
        "p_partkey": np.arange(n, dtype=np.int32),
        "p_brand": rng.integers(0, N_BRANDS, n).astype(np.int32),
        "p_container": rng.integers(0, N_CONTAINERS, n).astype(np.int32),
        "p_retailprice": (90000 + (np.arange(n) % 20001) * 10).astype(np.int32),
        "p_size": rng.integers(1, 51, n).astype(np.int32),
    }


def _customer(sf: float, seed: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    n = rows("customer", sf)
    return {
        "c_custkey": np.arange(n, dtype=np.int32),
        "c_mktsegment": rng.integers(0, N_MKTSEGMENTS, n).astype(np.int32),
    }


def _orders(sf: float, seed: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    n = rows("orders", sf)
    custkey = rng.integers(0, rows("customer", sf), n).astype(np.int32)
    orderdate = rng.integers(DATE_MIN_DAYS, DATE_MAX_DAYS - 151, n).astype(np.int32)
    priority = rng.integers(0, N_ORDERPRIORITIES, n).astype(np.int32)
    totalprice = rng.integers(90_000, 55_000_00, n).astype(np.int32)
    return {
        "o_orderkey": np.arange(n, dtype=np.int32),
        "o_custkey": custkey,
        "o_orderdate": orderdate,
        "o_shippriority": np.zeros(n, np.int32),
        "o_orderpriority": priority,
        "o_totalprice": totalprice,
    }


def _lineitem(sf: float, seed: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    n = rows("lineitem", sf)
    partkey = rng.integers(0, rows("part", sf), n).astype(np.int32)
    qty = rng.integers(1, 51, n).astype(np.int32)
    price = qty * (90000 + (partkey % 2000) * 100)
    orderdate = rng.integers(DATE_MIN_DAYS, DATE_MAX_DAYS - 151, n)
    shipdate = (orderdate + rng.integers(1, 122, n)).astype(np.int32)
    orderkey = rng.integers(0, rows("orders", sf), n).astype(np.int32)
    discount = rng.integers(0, 11, n).astype(np.int32)
    tax = rng.integers(0, 9, n).astype(np.int32)
    returnflag = rng.integers(0, N_RETURNFLAGS, n).astype(np.int32)
    linestatus = rng.integers(0, N_LINESTATUS, n).astype(np.int32)
    commitdate = (orderdate + rng.integers(30, 91, n)).astype(np.int32)
    receiptdate = (shipdate + rng.integers(1, 31, n)).astype(np.int32)
    shipmode = rng.integers(0, N_SHIPMODES, n).astype(np.int32)
    return {
        "l_orderkey": orderkey,
        "l_partkey": partkey,
        "l_quantity": qty,
        "l_extendedprice": price.astype(np.int32),
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipmode": shipmode,
    }


MAKERS = {"part": _part, "customer": _customer, "orders": _orders, "lineitem": _lineitem}
SEED_OFFSET = {"part": 1, "customer": 2, "orders": 3, "lineitem": 4}


def generate(sf: float, seed: int, tables=tuple(MAKERS)) -> dict[str, dict[str, np.ndarray]]:
    """``{table: {column: array}}`` for ``tables`` at ``sf`` from ``seed``."""
    return {t: MAKERS[t](sf, seed + SEED_OFFSET[t]) for t in tables}
