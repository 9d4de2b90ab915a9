"""Device milliseconds per step in Pallas kernels (the trace's
custom-call events), on the first device."""


def read(view):
    if view["trace"] is None:
        return None
    dev = view["trace"]["devices"][0]
    return 1e3 * dev["pallas_s"] / dev["steps"]
