"""The paper end-to-end: distributed TPC-H over the scheduled exchange.

    JAX_PLATFORMS=cpu python examples/distributed_query.py   # 8 fake CPU devices
    python examples/distributed_query.py                     # every device present

Runs Q1/Q6/Q17/Q3 plus the plan-only Q4/Q12/Q18 through the cost-based
query planner on a mesh over every device (on the CPU, 8 fake devices: the
paper's 6-server cluster, rounded up to a power of two) and checks every
result against the numpy oracle.  Q17
is the paper's own worked example (Fig 6): the planner broadcasts the
filtered part side per the hybrid threshold and shares one lineitem
shuffle — its ``explain()`` is printed first.
"""

import os, sys

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # the flag must precede JAX's initialization; it shapes only the CPU
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.relational import datagen, distributed as D, oracle
from repro.relational.context import ExecutionContext


def main():
    sf = 0.02
    print(f"generating TPC-H SF={sf} ...")
    tabs = datagen.gen_all(sf)
    li, part = tabs["lineitem"], tabs["part"]
    cust, orders = tabs["customer"], tabs["orders"]
    n = ExecutionContext(num_shards=jax.device_count())

    # the cost-based planner's view of Q17 (the paper's Fig 6 example)
    from repro.relational.planner import tpch

    print(tpch.explain_query(tpch.q17(), tpch.tpch_catalog(sf), n))

    r1 = D.q1_distributed(li, n)
    o1 = oracle.q1_oracle(li)
    ok1 = all(np.allclose(np.asarray(r1[k]), o1[k], rtol=1e-4) for k in o1)
    print(f"Q1  (pre-aggregation, no shuffle)      ok={ok1}")

    r6 = float(D.q6_distributed(li, n))
    print(f"Q6  (filter+sum)                       ok={np.isclose(r6, oracle.q6_oracle(li), rtol=1e-4)}")

    r17 = float(D.q17_distributed(li, part, n))
    o17 = oracle.q17_oracle(li, part)
    print(f"Q17 (partition+broadcast, paper Fig 6) ok={np.isclose(r17, o17, rtol=1e-3)}  value={r17:,.0f}")

    r3 = D.q3_distributed(cust, orders, li, n)
    o3 = oracle.q3_oracle(cust, orders, li)
    got = dict(zip(np.asarray(r3["o_orderkey"]).tolist(), np.asarray(r3["revenue"]).tolist()))
    ok3 = set(got) == set(o3["o_orderkey"].tolist())
    print(f"Q3  (broadcast customer + shuffle + top-10) ok={ok3}")
    print("top-3:", sorted(got.items(), key=lambda kv: -kv[1])[:3])

    r4 = D.q4_distributed(li, orders, n)
    ok4 = np.allclose(np.asarray(r4["order_count"]), oracle.q4_oracle(li, orders))
    print(f"Q4  (EXISTS via distinct-keys build)   ok={ok4}")

    r12 = D.q12_distributed(li, orders, n)
    o12 = oracle.q12_oracle(li, orders)
    ok12 = np.allclose(r12["high_line_count"], o12["high_line_count"]) and \
        np.allclose(r12["low_line_count"], o12["low_line_count"])
    print(f"Q12 (co-partition + dense group-by)    ok={ok12}")

    r18 = D.q18_distributed(li, orders, cust, n)
    o18 = oracle.q18_oracle(li, orders, cust)
    ok18 = sorted(np.asarray(r18["o_orderkey"]).tolist()) == \
        sorted(o18["o_orderkey"].tolist())
    print(f"Q18 (HAVING + two joins + top-100)     ok={ok18}")


if __name__ == "__main__":
    main()
