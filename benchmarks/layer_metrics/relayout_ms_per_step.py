"""Device milliseconds per step in ops that only move or re-tile data
(``copy``, ``reshape``, ``transpose``), on the first device: what the
layouts the compiler has to reconcile around the kernels cost."""


def read(view):
    if view["trace"] is None:
        return None
    dev = view["trace"]["devices"][0]
    return 1e3 * dev["relayout_s"] / dev["steps"]
