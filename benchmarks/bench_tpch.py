"""Paper Table 2 analogue: TPC-H through the query planner.

Every query — the six ported ones AND the plan-only Q4/Q12/Q18 — runs
through the declarative planner (logical IR -> cost-based exchange placement
-> shard_map executor) and is verified against the numpy oracle.  For the
queries that still have a hand-written local pipeline (``queries.py``) the
bench reports planned-vs-handwritten jit wall time on this host — the "does
the abstraction cost anything" number.  Wall time on fake CPU devices is NOT
a network measurement, so the distributed dimension is reported as the
planner's modeled exchange profile instead: shuffle/broadcast edge counts
and wire bytes per query at 8 shards (the paper's "data shuffled" row),
straight from the physical plan that the golden snapshots pin down.

The modeled numbers are checked against measurement: the bench runs
``repro.obs.model_check`` (a traced run — on a TPU in this process over the
chips present, elsewhere in a subprocess on an 8-fake-device mesh, since
the XLA device-count flag must precede jax init) and records each edge's ``byte_model_err`` — measured wire bytes from the
in-jit destination histograms vs the planner's estimate-priced model.
``--compare`` gates those leaves lower-is-better at the usual 2x.

``run(smoke=True)`` returns the record the CI ``bench-smoke`` job writes to
``BENCH_tpch.json`` — the per-PR perf trajectory for the relational engine.
"""

import jax
import numpy as np

from repro.relational import datagen, oracle, queries
from repro.relational.planner import compile_plan, tpch as T
from .common import emit, time_jit

SF = 0.02
PLAN_SHARDS = 8  # the exchange-profile mesh (modeled, no devices needed)

# model-vs-measured subprocess runs: (query, streamed) — q17 streamed is
# the hardest case (selective semi-join upstream, two passes over the
# shared shuffle); q3 exercises the resident-side traversal accounting.
MODEL_CHECKS = (("q17", True), ("q3", True))
SMOKE_MODEL_CHECKS = (("q17", True),)


def _check_shards() -> int:
    """Mesh size of the model check: the chips present on a TPU, else
    ``PLAN_SHARDS`` fake CPU devices."""
    return jax.device_count() if jax.default_backend() == "tpu" else PLAN_SHARDS


def _model_check(query: str, streamed: bool, trace_dir: str | None) -> dict:
    """One traced query under ``repro.obs.model_check``.

    On a TPU it runs in this process on the chips present (a chip belongs
    to one process, so a child could not take it); elsewhere in a child on
    ``PLAN_SHARDS`` fake CPU devices.
    """
    import json
    import os
    import subprocess
    import sys

    if jax.default_backend() == "tpu":
        import argparse

        from repro.obs import model_check

        return model_check._cli_run(argparse.Namespace(
            query=query, sf=0.01, shards=_check_shards(), pods=1,
            streamed=streamed, morsel_rows=0, trace_dir=trace_dir,
            bound=model_check.BYTE_MODEL_BOUND,
        ))

    cmd = [sys.executable, "-m", "repro.obs.model_check",
           "--query", query, "--shards", str(PLAN_SHARDS)]
    if streamed:
        cmd.append("--streamed")
    if trace_dir:
        cmd += ["--trace-dir", trace_dir]
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={PLAN_SHARDS}"
    out = subprocess.run(cmd, capture_output=True, text=True, env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"model_check {query} exited {out.returncode}:\n"
            f"{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout)


def _handwritten_runners(tabs):
    """jit'd hand-written local pipelines, keyed by query name."""
    li, part = tabs["lineitem"], tabs["part"]
    cust, orders = tabs["customer"], tabs["orders"]
    mk = type(li)
    return {
        "q1": (
            jax.jit(lambda t, v: queries.q1_local(mk(t, v, li.dictionaries), 90)),
            (li.columns, li.valid),
        ),
        "q6": (
            jax.jit(lambda t, v: queries.q6_local(mk(t, v, li.dictionaries))),
            (li.columns, li.valid),
        ),
        "q17": (
            jax.jit(lambda lc, lv, pc, pv: queries.q17_local(
                mk(lc, lv, li.dictionaries), mk(pc, pv, part.dictionaries))),
            (li.columns, li.valid, part.columns, part.valid),
        ),
        "q3": (
            jax.jit(lambda cc, cv, oc, ov, lc, lv: queries.q3_local(
                mk(cc, cv), mk(oc, ov), mk(lc, lv))["revenue"]),
            (cust.columns, cust.valid, orders.columns, orders.valid,
             li.columns, li.valid),
        ),
        "q14": (
            jax.jit(lambda lc, lv, pc, pv: queries.q14_finalize(
                *queries.q14_local(mk(lc, lv, li.dictionaries),
                                   mk(pc, pv, part.dictionaries)))),
            (li.columns, li.valid, part.columns, part.valid),
        ),
        "q19": (
            jax.jit(lambda lc, lv, pc, pv: queries.q19_local(
                mk(lc, lv, li.dictionaries), mk(pc, pv, part.dictionaries))),
            (li.columns, li.valid, part.columns, part.valid),
        ),
    }


def run(smoke: bool = False, trace_dir: str | None = None):
    sf = 0.01 if smoke else SF
    iters = 3 if smoke else 5
    tabs = datagen.gen_all(sf)
    all_tables = {
        "lineitem": tabs["lineitem"], "part": tabs["part"],
        "orders": tabs["orders"], "customer": tabs["customer"],
    }
    hand = _handwritten_runners(tabs)
    record = {"sf": sf, "plan_shards": PLAN_SHARDS, "queries": {}}

    for name, factory in T.ALL_QUERIES.items():
        pq = factory()
        catalog = {t: all_tables[t].capacity for t in pq.tables}
        # the planner's distributed exchange profile (modeled at 8 shards)
        plan8 = pq.plan(catalog, PLAN_SHARDS)
        summary = plan8.exchange_summary()
        wire = plan8.total_wire_bytes()

        # planned single-device wall time + correctness (same host as the
        # hand-written baseline, so the numbers are comparable)
        plan1 = pq.plan(catalog, 1)
        runner = compile_plan(plan1, all_tables)
        t_planned = time_jit(runner, iters=iters)
        raw = runner()
        got = pq.finalize(raw) if pq.finalize else raw
        ok = oracle.matches(name, got, oracle.expected(name, tabs))

        t_hand = None
        if name in hand:
            fn, args = hand[name]
            t_hand = time_jit(fn, *args, iters=iters)
            emit(f"tpch/{name}_handwritten", f"{t_hand*1e3:.2f}", "ms",
                 f"SF={sf} local pipeline")
        emit(f"tpch/{name}_planned", f"{t_planned*1e3:.2f}", "ms",
             f"SF={sf} correct={ok}" + (
                 f" vs_handwritten={t_planned/t_hand:.2f}x" if t_hand else
                 " plan-only"))
        emit(f"tpch/{name}_wire_bytes", wire, "B",
             f"{len(plan8.shuffle_stats)} shuffle + "
             f"{len(plan8.broadcast_stats)} broadcast edges @ "
             f"{PLAN_SHARDS} shards")
        record["queries"][name] = {
            "correct": bool(ok),
            "planned_ms": round(t_planned * 1e3, 3),
            "handwritten_ms": round(t_hand * 1e3, 3) if t_hand else None,
            "wire_bytes": int(wire),
            "exchanges": summary,
        }

    record["model_check"] = {}
    for qname, streamed in (SMOKE_MODEL_CHECKS if smoke else MODEL_CHECKS):
        rep = _model_check(qname, streamed, trace_dir)
        worst = rep.get("worst_byte_model_err")
        record["model_check"][qname] = {
            "worst_byte_model_err": worst,
            "edges": {
                k: e["byte_model_err"] for k, e in rep["edges"].items()
            },
        }
        emit(f"tpch/{qname}_byte_model_err",
             f"{worst:.3f}" if worst is not None else "n/a", "x",
             f"measured vs modeled wire bytes @ {_check_shards()} devices"
             + (" (streamed)" if streamed else ""))
    return record


if __name__ == "__main__":
    run()
