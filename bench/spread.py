#!/usr/bin/env python3
"""The spread of a cell's runs, metric by metric, and the bound it calls for.

    python3 bench/spread.py RUN_OUTPUT [RUN_OUTPUT ...]

Each file holds the standard output of one or more runs of one cell: every
line that is a JSON object with ``metrics`` is one run's result line, and
other lines are skipped.  For each metric it prints the number of runs, the
median and two spreads, each a share of the median:

- ``iqr``: the distance between the first and the third quartile, as
  ``statistics.quantiles(values, n=4)`` gives them;
- ``range``: the range of the runs, less the run farthest from the median
  where leaving it out narrows the range (the benchmark's check reads a
  set of runs so);

and ``bound``: twice ``range``, rounded up to the next 0.5%, never under 1%.
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import sys


def result_lines(text: str) -> list[dict]:
    """The result objects among ``text``'s lines."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and isinstance(obj.get("metrics"), dict):
            out.append(obj)
    return out


def iqr_share(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def range_share(values: list[float]) -> float:
    med = statistics.median(values)
    kept = sorted(values)
    if len(kept) > 2:
        far = max(kept, key=lambda v: abs(v - med))
        rest = list(kept)
        rest.remove(far)
        if max(rest) - min(rest) < kept[-1] - kept[0]:
            kept = rest
    return (kept[-1] - kept[0]) / med


def bound_for(range_: float) -> float:
    """Twice the spread, rounded up to the next 0.5%, never under 1%."""
    return max(0.01, math.ceil(round(2 * range_ / 0.005, 9)) * 0.005)


def table(results: list[dict]) -> dict[str, dict]:
    values: dict[str, list[float]] = {}
    for r in results:
        for name, m in r["metrics"].items():
            values.setdefault(name, []).append(float(m["value"]))
    out = {}
    for name, vs in values.items():
        if len(vs) < 2:
            continue
        rng = range_share(vs)
        out[name] = {
            "n": len(vs),
            "median": statistics.median(vs),
            "iqr": iqr_share(vs),
            "range": rng,
            "bound": bound_for(rng),
        }
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("outputs", nargs="+", help="files of run output")
    args = p.parse_args(argv)
    results = []
    for path in args.outputs:
        with open(path) as f:
            results += result_lines(f.read())
    if len(results) < 2:
        print(f"bench/spread.py: {len(results)} result lines, need 2 or more", file=sys.stderr)
        return 2
    for name, row in table(results).items():
        print(json.dumps({"metric": name, **row}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
