"""Share of the traced window in which a chip runs no op, averaged over the
chips: 1 - (union of device op intervals) / (traced window)."""


def read(view):
    t = view.trace
    if not t.devices:
        return None
    return 1.0 - t.busy_s() / t.window_s()
