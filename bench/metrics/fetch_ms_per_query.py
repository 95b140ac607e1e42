"""Host milliseconds per query completed in the traced rounds spent in the
executor's ``repro.fetch``: after the device program is done, the drop
check, the device-to-host reads of the result and the exchange reports,
and the query-trace record."""

import spans


def read(view):
    return spans.ms_per_query(view, spans.FETCH)
