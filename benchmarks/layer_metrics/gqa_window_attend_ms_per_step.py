"""Device milliseconds per step under ``gqa.attend.window``: the
attention of the window layers (the band's tiles of q.k, softmax, p.v
and, in the backward, the sum of a key/value head's gradient over its
query heads), forward and backward."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "gqa.attend.window")
