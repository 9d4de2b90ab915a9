"""Device milliseconds per step under ``gqa.attend.sparse``: the main
attention over the selected pairs (the selection kernels' forward and
one-kernel backward, the tiles' table, the sum of a key/value head's
gradient over its query heads) and the head-summed distribution the
indexer learns from (forward and rebuilt forward); ``remat = 1`` keeps
the kernel's output and logsumexp, so no rebuilt attention forward."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "gqa.attend.sparse")
