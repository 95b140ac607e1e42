"""Share of the traced window in which a chip is idle while the host is in
the serving path's own work (``repro.plan``, ``repro.dispatch``,
``repro.fetch`` or ``repro.finalize``), averaged over the chips: the part of
``device_idle_share`` that the program's host work explains.  It never
exceeds ``device_idle_share``."""

import spans


def read(view):
    t = view.trace
    if not t.devices or not spans.named(t, spans.SERVE + spans.FETCH):
        return None
    work = spans.covered(t, spans.SERVE + spans.FETCH)
    lo, hi = t.window
    shares = [spans.overlap(t.gaps(c), work) / (hi - lo) for c in t.devices]
    return sum(shares) / len(shares)
