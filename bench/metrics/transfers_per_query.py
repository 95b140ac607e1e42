"""Device-to-host reads (``repro.transfer`` events, one per array
``compat.fetch`` reads) that start in the traced window, per query completed
in the traced rounds."""

import spans


def read(view):
    t = view.trace
    lo, hi = t.window
    n = sum(lo <= e.start < hi for e in spans.named(t, (spans.TRANSFER,)))
    if not n or not view.queries:
        return None
    return n / view.queries
