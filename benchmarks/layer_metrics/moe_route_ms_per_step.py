"""Device milliseconds per step under ``moe.route``: the routers' scores,
top-k, the sort of the pairs by expert and the bias's update, forward,
rebuilt forward and backward."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "moe.route")
