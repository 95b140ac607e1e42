"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig10|skew|conn|tpch|fig3|fig12|kern|serve|qserve|oocore|roofline]
    PYTHONPATH=src python -m benchmarks.run --smoke [--json-dir artifacts/bench]
    PYTHONPATH=src python -m benchmarks.run --compare BASELINE[.json] [--json-dir artifacts/bench]

Emits ``name,value,unit,note`` CSV lines.  ``--smoke`` runs the reduced
CI lane — the static-vs-continuous serve comparison, the exchange pack
A/B, the planned-TPC-H sweep, the adaptive-optimizer skew scenario, the
query-serving warm-vs-cold + multi-tenant QPS check, and the out-of-core
streamed-vs-resident comparison — and writes ``BENCH_serve.json`` /
``BENCH_exchange.json`` / ``BENCH_tpch.json`` / ``BENCH_skew.json`` /
``BENCH_qserve.json`` / ``BENCH_oocore.json`` under ``--json-dir``; the CI
``bench-smoke`` job uploads those as artifacts, so the perf trajectory is
recorded per PR instead of living only in logs.

``--compare`` turns the trajectory into a gate: it diffs the fresh
records in ``--json-dir`` against a baseline (the previous run's uploaded
``BENCH_*.json``, a file or a directory of them) and exits nonzero if any
recorded metric regressed by more than ``--compare-threshold`` (default
2x — wide enough for shared-runner noise, narrow enough to catch a real
slowdown).  Direction is inferred from the metric name: times / bytes /
slot-steps are lower-is-better; ``tok_s`` and the ``*_ratio`` /
``*_fraction`` scores are higher-is-better; everything else (counts,
flags, tuned knobs) is informational and not gated.
The roofline section reads the dry-run artifacts (run
``python -m repro.launch.dryrun --device-kind "TPU v5 lite"`` first).
"""

import argparse
import glob as _glob
import json
import os
import sys

from repro.launch.compile_cache import enable_compile_cache

from . import (
    bench_autotune,
    bench_connections,
    bench_exchange,
    bench_kernels,
    bench_oocore,
    bench_qserve,
    bench_scaling,
    bench_schedule,
    bench_serve,
    bench_skew,
    bench_tpch,
)

SECTIONS = {
    "fig10": bench_schedule.run,     # Fig 10(b)/(c): scheduling vs contention
    "skew": bench_skew.run,          # §3.1 skew table
    "conn": bench_connections.run,   # §3.1 connection/buffer scaling
    "tpch": bench_tpch.run,          # Table 2: query runtimes + shuffle bytes
    "fig3": bench_scaling.run,       # Fig 3/11: scale-out per transport
    "fig12": bench_exchange.run,     # Fig 5/12(b) + MoE exchange A/B
    "kern": bench_kernels.run,       # kernel traffic models
    "autotune": bench_autotune.run,  # modeled vs measured multiplexer tuning
    "serve": bench_serve.run,        # static vs continuous batching
    "qserve": bench_qserve.run,      # multi-tenant query serving + plan cache
    "oocore": bench_oocore.run,      # out-of-core morsel streaming + prefetch
}


def roofline():
    import glob

    from repro.launch.roofline import format_table, from_artifact

    rows = []
    art_dir = "artifacts/dryrun_final" if glob.glob("artifacts/dryrun_final/*.json") else "artifacts/dryrun_v2"
    for f in sorted(glob.glob(art_dir + "/*.json")):
        art = json.load(open(f))
        if art.get("status") == "ok" and not art.get("tag"):
            rows.append(from_artifact(art))
    if rows:
        order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}
        rows.sort(key=lambda r: (r.mesh, r.arch, order[r.shape]))
        print(format_table(rows))
    else:
        print("roofline: no artifacts found (run repro.launch.dryrun first)")


def smoke(json_dir: str, trace_dir: str | None = None) -> None:
    """The CI bench lane: serve + exchange + tpch records -> BENCH_*.json."""
    os.makedirs(json_dir, exist_ok=True)
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
    print("# --- serve (smoke) ---")
    serve_rec = bench_serve.run(smoke=True)
    print("# --- fig12 (smoke) ---")
    exchange_rec = bench_exchange.run(smoke=True)
    print("# --- tpch (smoke) ---")
    tpch_rec = bench_tpch.run(smoke=True, trace_dir=trace_dir)
    print("# --- skew (smoke) ---")
    skew_rec = bench_skew.run(smoke=True)
    print("# --- qserve (smoke) ---")
    qserve_rec = bench_qserve.run(smoke=True, trace_dir=trace_dir)
    print("# --- oocore (smoke) ---")
    oocore_rec = bench_oocore.run(smoke=True)
    for name, rec in (("BENCH_serve.json", serve_rec),
                      ("BENCH_exchange.json", exchange_rec),
                      ("BENCH_tpch.json", tpch_rec),
                      ("BENCH_skew.json", skew_rec),
                      ("BENCH_qserve.json", qserve_rec),
                      ("BENCH_oocore.json", oocore_rec)):
        path = os.path.join(json_dir, name)
        with open(path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
        print(f"# wrote {path}")


# Metric-direction inference for --compare.  Checked against the LEAF key
# of each dotted path; higher-is-better wins ties (tok_s ends in "_s" but
# is a throughput).  Unmatched keys (counts, knobs, flags) are not gated.
_HIGHER_IS_BETTER = ("tok_s", "_ratio", "_fraction")
# _model_err: measured-vs-modeled exchange-byte ratio (>= 1, 1 = perfect
# model) — a growing ratio means the planner's estimates are drifting from
# what the devices ship, so it gates lower-is-better like a latency.
_LOWER_IS_BETTER = (
    "_s", "_ms", "_us", "_bytes", "slot_steps", "_steps", "_model_err"
)


def _direction(path: str) -> str | None:
    leaf = path.rsplit(".", 1)[-1]
    if any(leaf.endswith(s) for s in _HIGHER_IS_BETTER):
        return "higher"
    if any(leaf.endswith(s) for s in _LOWER_IS_BETTER):
        return "lower"
    return None


def _numeric_leaves(obj, prefix: str = "") -> dict:
    """Flatten a JSON record to {dotted.path: float} over numeric leaves."""
    out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(_numeric_leaves(v, f"{prefix}{k}."))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            out.update(_numeric_leaves(v, f"{prefix}{i}."))
    elif isinstance(obj, bool):
        pass
    elif isinstance(obj, (int, float)):
        out[prefix.rstrip(".")] = float(obj)
    return out


def compare(baseline: str, json_dir: str, threshold: float = 2.0) -> int:
    """Gate the fresh BENCH_*.json in json_dir against a recorded baseline.

    ``baseline`` is either one BENCH_*.json file or a directory of them
    (the previous CI run's artifact).  Returns the number of regressions:
    gated metrics present in BOTH records whose ratio worsened past
    ``threshold``.  Metrics only in one side are reported but never fail —
    benches may be added or retired without poisoning the gate.
    """
    if os.path.isdir(baseline):
        base_files = sorted(_glob.glob(os.path.join(baseline, "BENCH_*.json")))
    else:
        base_files = [baseline]
    if not base_files:
        print(f"# compare: no BENCH_*.json under {baseline!r} — nothing to gate")
        return 0

    regressions = []
    for bf in base_files:
        name = os.path.basename(bf)
        ff = os.path.join(json_dir, name)
        if not os.path.exists(ff):
            print(f"# compare: {name}: no fresh record in {json_dir} — skipped")
            continue
        with open(bf) as f:
            base_leaves = _numeric_leaves(json.load(f))
        with open(ff) as f:
            fresh_leaves = _numeric_leaves(json.load(f))
        gated = checked = 0
        for path, bval in sorted(base_leaves.items()):
            d = _direction(path)
            if d is None or path not in fresh_leaves:
                continue
            checked += 1
            fval = fresh_leaves[path]
            if bval <= 0.0:
                continue  # ratio undefined; nothing sane to gate against
            ratio = fval / bval
            worse = ratio > threshold if d == "lower" else ratio < 1.0 / threshold
            if worse:
                gated += 1
                regressions.append((name, path, d, bval, fval, ratio))
        print(f"# compare: {name}: {checked} metrics checked, {gated} regressed")

    for name, path, d, bval, fval, ratio in regressions:
        print(f"REGRESSION {name}:{path} ({d} is better): "
              f"{bval:.6g} -> {fval:.6g} ({ratio:.2f}x)")
    if not regressions:
        print(f"# compare: OK — no metric regressed past {threshold}x")
    return len(regressions)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default="all")
    p.add_argument("--smoke", action="store_true",
                   help="reduced CI lane; writes BENCH_*.json to --json-dir")
    p.add_argument("--json-dir", default=os.path.join("artifacts", "bench"))
    p.add_argument("--trace-dir", default=None,
                   help="also write Perfetto/JSON traces of the traced "
                        "smoke benches here (uploaded as CI artifacts)")
    p.add_argument("--compare", default=None, metavar="BASELINE",
                   help="BENCH_*.json file or directory to gate --json-dir "
                        "against; exits nonzero on any regression")
    p.add_argument("--compare-threshold", type=float, default=2.0,
                   help="worsening ratio that counts as a regression")
    args = p.parse_args()
    if args.smoke or args.compare is None:  # --compare alone compiles nothing
        enable_compile_cache()
    print("name,value,unit,note")
    if args.smoke:
        smoke(args.json_dir, trace_dir=args.trace_dir)
    if args.compare is not None:
        n = compare(args.compare, args.json_dir, args.compare_threshold)
        sys.exit(1 if n else 0)
    if args.smoke:
        return
    for name, fn in SECTIONS.items():
        if args.only in ("all", name):
            print(f"# --- {name} ---")
            fn()
    if args.only in ("all", "roofline"):
        print("# --- roofline (from dry-run artifacts) ---")
        roofline()


if __name__ == "__main__":
    main()
