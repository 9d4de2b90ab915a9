"""Device milliseconds per step under ``gqa.select``: the exact choice
of each query's 2048 keys from the indexer's scores (the threshold found
bit by bit over the square, the ties' cut, the int8 set) and the count of
what was chosen; forward only: ``remat = 1`` keeps the set."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "gqa.select")
