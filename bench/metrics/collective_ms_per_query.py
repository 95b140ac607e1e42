"""Device milliseconds of collective ops (all-to-all, collective-permute,
all-gather, all-reduce, reduce-scatter, their -start and -done halves) on
the busiest chip, per query completed in the traced rounds.  An async
collective's transfer overlaps other ops and is not on this line: what is
counted is the time the chip spends in the collective instructions
themselves, waits on a ``-done`` included."""

import re

COLLECTIVE = re.compile(r"^%?(all-to-all|collective-permute|all-gather|all-reduce|reduce-scatter)")


def read(view):
    t = view.trace
    if not t.devices or not view.queries:
        return None
    per_chip = [
        sum(s for (_, op), s in t.op_seconds(c).items() if COLLECTIVE.match(op))
        for c in t.devices
    ]
    if not any(per_chip):
        return None
    return max(per_chip) * 1e3 / view.queries
