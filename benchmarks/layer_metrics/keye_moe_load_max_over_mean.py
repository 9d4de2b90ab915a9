"""The largest held expert's load over the mean load of all experts at
the last drained step, the worst of the expert layers
(``cxxnet_moe_load_max_over_mean``): 1 is a balanced router. This router
has neither a selection bias nor an auxiliary loss, and on a repeated
batch the number is the step's it is read at (PERF.md section 3)."""

from benchmarks.joyai_reads import gauge_max


def read(view):
    return gauge_max("cxxnet_moe_load_max_over_mean")
