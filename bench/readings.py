#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, many seeds in one
process.

    python3 bench/readings.py --workload <cell> --seeds 101 102 ... [--seconds 1]

For each seed it prints two lines: the system's ``rel_err`` and ``wrong``,
from a run of the cell itself (the same set-up, engine and compiled programs
as ``run.py``, with a short window: the warm-up request of each template and
the rounds that start within ``--seconds``), and the control's: the
reference computed in bfloat16, put in the system's place and compared at
the cell's size.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def control_reading(cell, seed: int) -> dict:
    """``rel_err`` and ``wrong`` of the bfloat16 control over every template
    of the cell's mix."""
    import compare
    import reference
    import tpch_data

    names = list(dict.fromkeys(cell.traffic["templates"]))
    tables = sorted({t for n in names for t in reference.TABLES[n]})
    data = tpch_data.generate(cell.config["scale_factor"], seed, tables)
    v = compare.Verdict()
    for n in names:
        v.answer(n, reference.control(n, data), reference.expected(n, data))
    return {"rel_err": v.rel_err, "wrong": v.wrong}


def main(argv=None) -> int:
    import cell as cells
    import run

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=1.0)
    args = p.parse_args(argv)
    c = cells.resolve(args.workload)
    run.set_jax_env()
    sys.path.insert(0, str(HERE.parent / "src"))
    try:
        devices = run.tpu_devices(c.chips)
    except run.NoChip as e:
        print(f"bench/readings.py: {e}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        res = run.run_cell(c, seed, args.seconds, False, devices)
        gc.collect()
        system = {k: v["value"] for k, v in res["checks"].items()}
        print(json.dumps({"seed": seed, "side": "system", "correct": res["correct"], **system}),
              flush=True)
        print(json.dumps({"seed": seed, "side": "control", **control_reading(c, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
