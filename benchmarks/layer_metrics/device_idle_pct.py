"""Share of the traced window in which no op ran on the device:
1 - (union of op intervals) / window, mean over the cell's devices."""


def read(view):
    if view["trace"] is None:
        return None
    idle = [1.0 - d["busy_s"] / d["window_s"]
            for d in view["trace"]["devices"]]
    return 100.0 * sum(idle) / len(idle)
