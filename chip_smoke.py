#!/usr/bin/env python3
"""Bring-up check of the served TPC-H path on a TPU.

    python chip_smoke.py              # one chip: TPC-H SF 1
    python chip_smoke.py --chips 4    # a 2x2 v5e host: SF 4 over four chips

One chip: generates TPC-H at SF 1 from ``--seed`` (6M lineitem rows, resident
in HBM), builds a ``QueryServeEngine`` (the path ``repro.launch.qserve``
drives) on a one-device mesh with the Q1/Q3/Q6/Q17/Q18 template mix, serves
two requests of each template across two tenants, and checks
every answer against ``relational/oracle.py``.  Per template it prints the
compile seconds (tracing, lowering, and XLA compilation or a persistent-cache
load during the template's first request), the warm run seconds (dispatch to
``block_until_ready`` of the cached executor) and the device's peak bytes in
use so far.

Four chips (``--chips 4``): SF 4, so each chip holds what the one chip holds
at SF 1.  Q3/Q17/Q18 are served (one request each, after all executors
compile in parallel) on a one-device mesh, on a flat 4-way mesh
(the tuner's knobs, then ``pack_impl="pallas"`` pinned) and on the two-level
``(pod, q)`` 2x2 mesh.  Every answer must match the oracle's, and equal the
one-device mesh's in every integer field (keys, counts) with float fields
within ``MESH_RTOL``; every executor that packs with Pallas must hold a
``tpu_custom_call`` in its compiled HLO.

The last line of stdout is one JSON object: ``{"ok": ..., "device":
{"platform", "kind", "count"}}``.  Any failed phase, or a JAX that finds no
TPU, makes ``ok`` false and the exit code non-zero; nothing falls back to
the CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ONE_CHIP_MIX = ("q1", "q3", "q6", "q17", "q18")
FOUR_CHIP_MIX = ("q3", "q17", "q18")
TENANTS = ("tenant0", "tenant1")
REQUESTS = 2  # per template, one chip; the four-chip phase serves one
# Float fields of a multi-chip answer vs the one-device mesh's: the mesh
# changes f32 summation order, so they may differ in their last bits.  A
# tenth of the oracle's loosest money-sum tolerance (Q17's 1e-3).
MESH_RTOL = 1e-4
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), read from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def timed(label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    log(f"{label}: {time.perf_counter() - t0} s")
    return out


def peak_bytes() -> int:
    """Largest ``peak_bytes_in_use`` over the devices, so far."""
    import jax

    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())


def rel_errors(got, want) -> dict:
    """Largest relative error per numeric field of a result vs the oracle."""
    import numpy as np

    if not isinstance(want, dict):
        got, want = {"value": got}, {"value": want}
    out = {}
    for k, w in want.items():
        g = np.asarray(got.get(k), np.float64) if k in got else None
        w = np.asarray(w, np.float64)
        if g is None or g.shape != w.shape:
            out[k] = f"shape {None if g is None else g.shape} vs {w.shape}"
        else:
            out[k] = float(np.max(np.abs(g - w) / np.maximum(np.abs(w), 1e-30)))
    return out


def mesh_gap(got, ref):
    """Largest relative gap over the float fields of two answers to one
    template, or ``None`` when a shape or an integer field differs."""
    import numpy as np

    if not isinstance(ref, dict):
        got, ref = {"value": got}, {"value": ref}
    gap = 0.0
    for k, r in ref.items():
        g, r = np.asarray(got[k]), np.asarray(r)
        if g.shape != r.shape:
            return None
        if not np.issubdtype(r.dtype, np.floating):
            if not np.array_equal(g, r):
                return None
            continue
        rel = np.abs(g.astype(np.float64) - r) / np.maximum(np.abs(r), 1e-30)
        gap = max(gap, float(np.max(rel, initial=0.0)))
    return gap


def load_tpch(sf: float, seed: int, mix):
    """TPC-H at ``sf`` from ``seed`` and the oracle's answer per template."""
    from repro.relational import datagen, oracle

    tabs = timed(f"datagen SF {sf}", lambda: datagen.gen_all(sf, seed=seed))
    log("rows: " + ", ".join(f"{k}={t.capacity}" for k, t in tabs.items()))
    want = timed(
        "oracle", lambda: {name: oracle.expected(name, tabs) for name in mix}
    )
    return tabs, want


def make_engine(tabs, ctx, mix):
    """A ``QueryServeEngine`` on ``ctx``'s mesh with ``mix`` as templates."""
    from repro.relational.planner import tpch
    from repro.serve import QueryServeEngine

    templates = [tpch.ALL_QUERIES[name]() for name in mix]
    tables = {t: tabs[t] for t in sorted({t for pq in templates for t in pq.tables})}
    engine = QueryServeEngine(tables, ctx, num_slots=len(mix), templates=templates)
    return engine, templates


def runner_of(engine, pq):
    """The memoized executor the engine serves template ``pq`` with."""
    from repro.serve import QueryRequest

    return engine._runner(QueryRequest(tenant=TENANTS[0], query=pq))


def serve_mix(label, engine, templates, requests, warm_runs, clock):
    """Serve ``requests`` requests per template through ``engine``.

    The first request of each template is served alone, so the compile
    seconds it adds are its own; its executor then runs ``warm_runs`` more
    times.  Returns ``({template: [results]}, {template: stats})``.
    """
    import jax

    from repro.serve import QueryRequest

    results = {pq.name: [] for pq in templates}
    stats = {}
    for i, pq in enumerate(templates):
        req = QueryRequest(tenant=TENANTS[i % 2], query=pq)
        c0 = clock.seconds
        engine.serve([req])
        compile_s = clock.seconds - c0
        results[pq.name].append(req.result)
        runner = runner_of(engine, pq)
        warm = []
        for _ in range(warm_runs):
            t0 = time.perf_counter()
            jax.block_until_ready(runner.dispatch())
            warm.append(time.perf_counter() - t0)
        stats[pq.name] = dict(
            compile_s=compile_s, warm_run_s=min(warm), first_ttfr_s=req.ttfr_s,
            peak_bytes_in_use=peak_bytes(),
        )
    rest = [
        QueryRequest(tenant=TENANTS[(i + k) % 2], query=pq)
        for k in range(1, requests)
        for i, pq in enumerate(templates)
    ]
    for req in engine.serve(rest):
        results[req.query.name].append(req.result)
    rec = engine.record()
    log(f"[{label}] served={rec['served']} rounds={rec['rounds']} "
        f"ttfr_p50_s={rec.get('ttfr_p50_s')} ttfr_p99_s={rec.get('ttfr_p99_s')} "
        f"cache={json.dumps(rec['cache'], sort_keys=True)}")
    return results, stats


def one_chip(args, clock) -> bool:
    from repro.relational import oracle
    from repro.relational.context import ExecutionContext

    tabs, want = load_tpch(args.sf, args.seed, ONE_CHIP_MIX)
    engine, templates = make_engine(
        tabs, ExecutionContext(num_shards=1), ONE_CHIP_MIX
    )
    results, stats = serve_mix("1 chip", engine, templates, REQUESTS, 3, clock)
    ok = True
    for name in ONE_CHIP_MIX:
        good = [oracle.matches(name, r, want[name]) for r in results[name]]
        if not all(good):
            log(f"query={name} mismatch: {rel_errors(results[name][0], want[name])}")
        s = stats[name]
        log(f"query={name} ok={all(good)} requests={len(good)} "
            f"compile_s={s['compile_s']} warm_run_s={s['warm_run_s']} "
            f"first_ttfr_s={s['first_ttfr_s']} "
            f"peak_bytes_in_use={s['peak_bytes_in_use']}")
        ok &= all(good)
    log(f"persistent compile-cache hits: {clock.cache_hits}")
    return ok


def four_chips(args, clock) -> bool:
    from concurrent.futures import ThreadPoolExecutor

    from repro.relational import oracle
    from repro.relational.context import ExecutionContext

    tabs, want = load_tpch(args.sf, args.seed, FOUR_CHIP_MIX)
    meshes = (
        ("one-device", ExecutionContext(num_shards=1)),
        ("flat4", ExecutionContext(num_shards=4)),
        ("flat4-pallas", ExecutionContext(num_shards=4, pack_impl="pallas")),
        ("pod2x2", ExecutionContext(num_shards=4, num_pods=2)),
    )
    engines = {label: make_engine(tabs, ctx, FOUR_CHIP_MIX) for label, ctx in meshes}
    runners = {
        (label, pq.name): runner_of(engine, pq)
        for label, (engine, templates) in engines.items()
        for pq in templates
    }
    # Compile every executor at once (XLA compiles off the GIL); the jitted
    # executors then find their programs in JAX's in-process cache.
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(runners)) as pool:
        hlo = dict(zip(runners, pool.map(
            lambda r: r._jfn.lower(*r._flat).compile().as_text(),
            runners.values(),
        )))
    log(f"compiled {len(runners)} executors in parallel: "
        f"{time.perf_counter() - t0} s")
    ok = True
    reference = {}
    for label, (engine, templates) in engines.items():
        results, stats = serve_mix(label, engine, templates, 1, 1, clock)
        mux = engine._mux
        log(f"[{label}] mux={json.dumps(mux.describe(), sort_keys=True, default=str)}")
        for name in FOUR_CHIP_MIX:
            s = stats[name]
            good = all(oracle.matches(name, r, want[name]) for r in results[name])
            if label == "one-device":
                reference[name] = results[name][0]
            gap = mesh_gap(results[name][0], reference[name])  # one request
            same = gap is not None and gap <= MESH_RTOL
            plan = runners[label, name]._plan
            pallas = mux.pack_impl == "pallas" and bool(plan.shuffle_stats)
            custom = "tpu_custom_call" in hlo[label, name]
            log(f"[{label}] query={name} ok={good} same_as_one_device={same} "
                f"float_gap={gap} "
                f"pack_impl={mux.pack_impl} pallas_pack={pallas} "
                f"tpu_custom_call={custom} compile_s={s['compile_s']} "
                f"warm_run_s={s['warm_run_s']} "
                f"peak_bytes_in_use={s['peak_bytes_in_use']}")
            ok &= good and same and (custom or not pallas)
    log(f"persistent compile-cache hits: {clock.cache_hits}")
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    args.sf = float(args.chips)  # SF 1 per chip: 6M lineitem rows each
    device = None
    ok = False
    try:
        import jax

        devs = jax.devices()
        device = {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
        log(f"platform={device['platform']} device_kind={device['kind']} "
            f"device_count={device['count']} jax={jax.__version__}")
        if device["platform"] != "tpu":
            raise RuntimeError(f"JAX found no TPU (platform {device['platform']!r})")
        if device["count"] < args.chips:
            raise RuntimeError(f"--chips {args.chips} needs {args.chips} devices")
        from repro.core.topology import chip_spec
        from repro.launch.compile_cache import enable_compile_cache

        spec = chip_spec(device["kind"])
        log(f"chip spec: {spec.name} peak_flops_bf16={spec.peak_flops_bf16} "
            f"hbm_bandwidth={spec.hbm_bandwidth}")
        log(f"compile cache: {enable_compile_cache()}")
        clock = CompileClock()
        ok = (one_chip if args.chips == 1 else four_chips)(args, clock)
    except Exception:
        traceback.print_exc()
        ok = False
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
