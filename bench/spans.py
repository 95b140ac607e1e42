"""The program's own host spans in a reduced trace.

The serving path wraps its stages in ``repro.*`` profiler annotations
(``src/repro/obs/trace.py``): per request ``repro.plan``, ``repro.dispatch``,
``repro.wait``, ``repro.fetch`` (one ``repro.transfer`` per device-to-host
read inside it) and ``repro.finalize``.  They run on the thread that holds
``bench.round``, so ``trace_reduce`` keeps them in ``Trace.host``.  A trace
of a program without them yields no spans, and the metrics that read them
no value.
"""

from __future__ import annotations

import trace_reduce

SERVE = ("repro.plan", "repro.dispatch", "repro.finalize")
FETCH = ("repro.fetch",)
TRANSFER = "repro.transfer"


def named(trace, names) -> list:
    return [e for e in trace.host if e.name in names]


def covered(trace, names) -> list[tuple[int, int]]:
    """The union of the ``names`` spans' intervals, clipped to the window."""
    lo, hi = trace.window
    return trace_reduce.union(named(trace, names), lo, hi)


def length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def overlap(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """ns covered by both of two sorted lists of disjoint intervals."""
    out, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(hi - lo, 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def ms_per_query(view, names) -> float | None:
    """Milliseconds of the window covered by the ``names`` spans, per query
    completed in the traced rounds."""
    t = view.trace
    if not view.queries or not named(t, names):
        return None
    return length(covered(t, names)) / 1e6 / view.queries
