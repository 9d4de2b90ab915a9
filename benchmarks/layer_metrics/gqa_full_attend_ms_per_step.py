"""Device milliseconds per step under ``gqa.attend.full``: the causal
attention of the full layers (q.k, softmax, p.v and, in the backward,
the sum of a key/value head's gradient over its query heads), forward
and backward; ``remat = 1`` keeps the kernel's output and logsumexp, so
no rebuilt forward."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "gqa.attend.full")
