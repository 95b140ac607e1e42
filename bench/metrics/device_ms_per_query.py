"""Device busy milliseconds per query completed in the traced rounds,
averaged over the chips."""


def read(view):
    t = view.trace
    if not t.devices or not view.queries:
        return None
    return t.busy_s() * 1e3 / view.queries
