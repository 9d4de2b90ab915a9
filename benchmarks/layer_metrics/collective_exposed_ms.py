"""Device milliseconds per step in which a collective runs and no other
op does, on the first device: what the mesh costs that compute does not
hide."""


def read(view):
    if view["trace"] is None:
        return None
    dev = view["trace"]["devices"][0]
    return 1e3 * dev["collective_exposed_s"] / dev["steps"]
