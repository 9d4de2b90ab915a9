"""Share of the window the loop's thread spent blocked in a batch fetch
from the feed (the benchmark's own span around each fetch), in percent.
About 0 where the input layer is bypassed."""


def read(view):
    waits = [t1 - t0 for name, t0, t1 in view["spans"] if name == "fetch"]
    if not waits or view["span_window_s"] <= 0:
        return None
    return 100.0 * sum(waits) / view["span_window_s"]
