#!/usr/bin/env python3
"""Run one cell with ``--trace 1`` as ``run.py`` does, and keep its trace.

    python3 bench/record_trace.py --workload <cell> --seed <n> --seconds <s> \
        --out <dir> [--rounds 1]

Prints ``run.py``'s result line, then one JSON line of what the trace held:
the traced rounds' mean wall time (``bench.round``) and each ``repro.*``
span's count and total milliseconds.  Writes ``<dir>/<cell>.pbtxt.gz``: the
first ``--rounds`` traced rounds with the Python tracer's frames left out
and every other host event kept, so the program's sub-millisecond spans
survive (``trace_reduce.load`` reads it; ``bench/tests/data`` holds such
cuts).  Needs the chips the cell asks for, as ``run.py`` does.
"""

import argparse
import contextlib
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def summary(trace) -> dict:
    rounds = trace.rounds
    out = {
        "rounds": len(rounds),
        "round_ms_mean": sum(r.end - r.start for r in rounds) / len(rounds) / 1e6,
        "spans": {},
    }
    for e in trace.host:
        if e.name.startswith("repro."):
            n, ms = out["spans"].get(e.name, (0, 0.0))
            out["spans"][e.name] = (n + 1, ms + (e.end - e.start) / 1e6)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--rounds", type=int, default=1)
    args = p.parse_args(argv)

    import run
    import trace_reduce

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    kept = Path(tempfile.mkdtemp(prefix="bench-trace-", dir=out))

    @contextlib.contextmanager
    def keep_dir(prefix=None):
        yield str(kept)

    run.tempfile.TemporaryDirectory = keep_dir  # run.py's trace dir, kept
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    if rc:
        return rc
    xplane = trace_reduce.find_xplane(kept)
    trace = trace_reduce.load(xplane)
    print(json.dumps(summary(trace)), flush=True)
    trace.host = [e for e in trace.host if not e.name.startswith("$")]
    text = trace_reduce.text_proto(trace, args.rounds, 0)
    cut = out / f"{args.workload}.pbtxt.gz"
    cut.write_bytes(gzip.compress(text.encode(), mtime=0))
    shutil.rmtree(kept)
    return 0


if __name__ == "__main__":
    sys.exit(main())
