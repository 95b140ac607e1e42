"""Host milliseconds of the serving engine's own stages per query completed
in the traced rounds: ``repro.plan`` (plan key, plan and executor caches),
``repro.dispatch`` (launching the program) and ``repro.finalize`` (the
template's host finalize), as the union of their intervals in the window."""

import spans


def read(view):
    return spans.ms_per_query(view, spans.SERVE)
