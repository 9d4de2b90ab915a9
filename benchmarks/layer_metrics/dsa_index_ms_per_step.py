"""Device milliseconds per step under ``gqa.index``: the indexer's three
projections, its key's LayerNorm, the rotary, and its scores of every
causal pair (the ``index_scores`` kernel), forward, rebuilt forward and
backward (XLA's, a chunk of queries at a time)."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "gqa.index")
