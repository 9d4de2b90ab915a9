"""Device milliseconds per step under ``moe.experts``: the gather of the
held pairs' rows and the grouped matrix products of the held experts,
forward, rebuilt forward and backward."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "moe.experts")
