#!/usr/bin/env python3
"""TPC-H query streams through ``QueryServeEngine``, measured on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``configs/<name>.json``: scale factor and mesh) and a traffic
mix (``traffic/<name>.json``: templates and streams).  One run:

1. makes the TPC-H tables from ``--seed`` and hands them to one
   ``QueryServeEngine`` on the configuration's mesh, with every template of
   the mix registered, then serves each template once: set-up ends there;
2. runs the window: S closed-loop streams, stream i sending the mix's
   templates in order from template i, one round (one query per stream)
   per ``engine.serve`` call, while less than ``--seconds`` has passed.  The
   window closes when the last round completes; each request is timed from
   its submission to its finalized answer;
3. with ``--trace 1``, records the profiler trace of ``trace_rounds``
   rounds after the first and reads the cell's per-layer metrics from it;
4. compares every answer served, warm-up included, with the numpy
   reference, and prints the numbers compared beside their limits, last on
   stderr and last in the result: the final stdout line, one JSON object.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

# JAX's persistent cache, at a fixed place in the checkout: the path is part
# of the cache key.  Programs that compile in under a second are kept too.
CACHE_DIR = HERE / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def set_jax_env() -> None:
    """Before JAX is imported: the benchmark's compile cache, and no libtpu
    log files outside the run's own directories."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # a few programs: no eviction
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def tpu_devices(chips: int) -> list:
    t0 = time.perf_counter()
    import jax

    t1 = time.perf_counter()
    devices = jax.devices()
    log(f"jax import {t1 - t0} s, devices {time.perf_counter() - t1} s")
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


class CompileCounter:
    """Persistent-cache hits and misses, and executables built (compiled or
    loaded from the cache), from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.hits = self.misses = self.builds = 0
        self.seconds: dict[str, float] = {}  # duration event -> total seconds
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.builds += 1
        self.seconds[event] = self.seconds.get(event, 0.0) + duration


class Phases:
    """Set-up's phases on the host clock: ``phases(name)`` ends the phase
    ``name``, which began where the last one ended (the first at ``T0``)."""

    def __init__(self):
        self.last = T0
        self.seconds: dict[str, float] = {}

    def __call__(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = now - self.last
        self.last = now


class Clock:
    """Wraps a template's finalize so the harness reads, on its own clock,
    when each answer is ready."""

    def __init__(self):
        self.ready: dict[int, float] = {}

    def wrap(self, pq):
        from jax.profiler import TraceAnnotation

        inner = pq.finalize

        def finalize(raw):
            with TraceAnnotation("bench.finalize"):
                out = inner(raw) if inner else raw
            self.ready[id(out)] = time.perf_counter()
            return out

        return dataclasses.replace(pq, finalize=finalize)


def percentile(values, q):
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Window:
    answers: list  # (template, result) of every request that completed
    latencies: list  # seconds, submission to finalized answer
    attempted: int
    failed: int
    close_s: float  # window start to the end of its last round
    traced_rounds: int
    traced: dict  # template -> queries completed in the traced rounds
    compiles: int  # executables built inside the window
    round_s: list = dataclasses.field(default_factory=list)  # each round's wall time
    gc_collections: list = dataclasses.field(default_factory=list)  # per generation
    error: str | None = None


def run_window(engine, templates, streams, seconds, clock, counter, trace_rounds, trace_dir):
    """Rounds of one query per stream until ``seconds`` have passed."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.serve import QueryRequest

    n = len(templates)
    w = Window([], [], 0, 0, 0.0, 0, {}, 0)
    builds0 = counter.builds
    gc0 = [g["collections"] for g in gc.get_stats()]
    tracing = False
    t0 = time.perf_counter()
    rnd = 0
    while time.perf_counter() - t0 < seconds:
        if trace_dir and rnd == 1:
            jax.profiler.start_trace(trace_dir)
            tracing = True
        reqs = [
            QueryRequest(tenant=f"stream{i}", query=templates[(rnd + i) % n])
            for i in range(streams)
        ]
        w.attempted += len(reqs)
        t_submit = time.perf_counter()
        try:
            with TraceAnnotation("bench.round"):
                done = engine.serve(reqs)
        except Exception as e:  # the round's answers never came
            w.failed += len(reqs)
            w.error = f"round {rnd}: {type(e).__name__}: {e}"
            break
        finally:
            rnd += 1
        w.round_s.append(time.perf_counter() - t_submit)
        for r in done:
            w.latencies.append(clock.ready.pop(id(r.result)) - t_submit)
            w.answers.append((r.query.name, r.result))
        w.failed += len(reqs) - len(done)
        if tracing:
            w.traced_rounds += 1
            for r in done:
                w.traced[r.query.name] = w.traced.get(r.query.name, 0) + 1
            if w.traced_rounds == trace_rounds:
                jax.profiler.stop_trace()
                tracing = False
    w.close_s = time.perf_counter() - t0
    w.compiles = counter.builds - builds0
    w.gc_collections = [g["collections"] - c for g, c in zip(gc.get_stats(), gc0)]
    if tracing:
        jax.profiler.stop_trace()
    return w


def end_to_end(name: str, w: Window, setup_s: float) -> float:
    if name == "qps":
        return len(w.latencies) / w.close_s
    if name in ("ttfr_p50_s", "ttfr_p50_host_s"):  # one quantity, bounded per cell kind
        return percentile(w.latencies, 50)
    if name == "ttfr_p95_s":
        return percentile(w.latencies, 95)
    if name == "setup_s":
        return setup_s
    raise KeyError(f"bench/run.py measures no end-to-end metric {name!r}")


@dataclasses.dataclass
class LayerView:
    """What a per-layer metric's ``read(view)`` may use."""

    trace: object  # trace_reduce.Trace
    traced: dict  # template -> queries completed in the traced rounds
    peaks: dict  # peaks.peaks(device_kind)
    pack_calls: dict  # template -> kernels.PackCall list of one request

    @property
    def queries(self) -> int:
        return sum(self.traced.values())


def run_cell(cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object."""
    phases = Phases()
    phases("jax_start")  # interpreter, JAX and the chip
    import compare
    import reference
    import tpch_data
    from repro.launch.compile_cache import enable_compile_cache
    from repro.relational.context import ExecutionContext
    from repro.relational.planner import tpch
    from repro.relational.table import from_numpy
    from repro.serve import QueryRequest, QueryServeEngine

    phases("imports")
    cfg, mix = cell.config, cell.traffic
    log(f"cell {cell.name}: SF {cfg['scale_factor']} on {cell.chips} chip(s), "
        f"templates {mix['templates']}, {mix['streams']} streams, seed {seed}")
    log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    names = list(dict.fromkeys(mix["templates"]))
    needed = sorted({t for n in names for t in reference.TABLES[n]})
    phases("compile_cache")
    data = tpch_data.generate(cfg["scale_factor"], seed, needed)
    phases("datagen")
    tables = {t: from_numpy(cols) for t, cols in data.items()}
    phases("placement")
    ctx = ExecutionContext(**cfg["mesh"])
    clock = Clock()
    by_name = {n: clock.wrap(tpch.ALL_QUERIES[n]()) for n in names}
    templates = [by_name[n] for n in mix["templates"]]
    engine = QueryServeEngine(
        tables, ctx, num_slots=mix["streams"], templates=list(by_name.values())
    )
    phases("engine")
    answers = []
    for n, pq in by_name.items():
        (req,) = engine.serve([QueryRequest(tenant="warmup", query=pq)])
        clock.ready.pop(id(req.result))
        answers.append((n, req.result))
    phases("warmup")
    import kernels

    pack_calls = kernels.pack_calls(engine, by_name.values())
    phases("pack_calls")
    setup_s = time.perf_counter() - T0
    log(f"setup_s={setup_s} persistent_cache_hits={counter.hits} "
        f"persistent_cache_misses={counter.misses} executables_built={counter.builds}")
    log(f"setup phases s: {phases.seconds} compile events s: {counter.seconds}")

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as trace_dir:
        w = run_window(
            engine, templates, mix["streams"], seconds, clock, counter,
            mix["trace_rounds"], trace_dir if trace else None,
        )
        thirds = [w.round_s[i * len(w.round_s) // 3:(i + 1) * len(w.round_s) // 3]
                  for i in range(3)]
        log(f"window: rounds={w.attempted // mix['streams']} completed={len(w.latencies)} "
            f"close_s={w.close_s} between_rounds_s={w.close_s - sum(w.round_s)} "
            f"longest_rounds_s={sorted(w.round_s)[-3:]} "
            f"median_round_s_by_third={[percentile(t, 50) for t in thirds if t]} "
            f"gc_collections={w.gc_collections} "
            f"compiles_in_window={w.compiles} error={w.error}")
        used = devices[: cell.chips]
        stats = [d.memory_stats() or {} for d in used]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        layer_trace = None
        if trace and w.traced_rounds:
            import trace_reduce

            layer_trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    answers += w.answers
    engine = tables = None
    gc.collect()

    verdict = compare.Verdict()
    for n in names:
        want = reference.expected(n, data)
        for got_name, got in answers:
            if got_name == n:
                verdict.answer(n, got, want)
    limits = cfg["limits"]
    checks = {
        "rel_err": {"value": verdict.rel_err, "limit": limits["rel_err"]},
        "wrong": {"value": verdict.wrong, "limit": limits["wrong"]},
        "answers": {"value": verdict.answers, "limit": len(names) + w.attempted},
    }
    correct = (
        w.failed == 0
        and verdict.answers == len(names) + w.attempted
        and verdict.rel_err <= limits["rel_err"]
        and verdict.wrong <= limits["wrong"]
    )
    dev0 = devices[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": correct,
        "attempted": w.attempted,
        "failed": w.failed,
        "metrics": {},
        "device": device,
    }
    if not trace:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {
                "value": end_to_end(m["name"], w, setup_s), "unit": m["unit"],
            }
    elif layer_trace is not None and layer_trace.window is not None:
        import cell as cells
        import peaks
        import trace_reduce

        view = LayerView(layer_trace, w.traced, peaks.peaks(dev0.device_kind), pack_calls)
        for m in cell.per_layer:
            value = cells.load_metric(m["name"], cell.root)(view)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = layer_trace.busy_s()
        device["window_s"] = layer_trace.window_s()
        if layer_trace.devices:
            program = frozenset(p.name for p in (HERE.parent / "src").rglob("*.py"))
            result["breakdown"] = trace_reduce.breakdown(layer_trace, program)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    import cell as cells

    c = cells.resolve(args.workload)
    set_jax_env()
    sys.path.insert(0, str(HERE.parent / "src"))
    try:
        devices = tpu_devices(c.chips)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    result = run_cell(c, args.seed % 2**63, args.seconds, bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
