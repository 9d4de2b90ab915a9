"""The largest held expert's load over the mean load of all experts at
the last drained step, the worst of the expert layers
(``cxxnet_moe_load_max_over_mean``): 1 is a balanced router."""

from benchmarks.joyai_reads import gauge_max


def read(view):
    return gauge_max("cxxnet_moe_load_max_over_mean")
