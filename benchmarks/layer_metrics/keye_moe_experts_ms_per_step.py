"""Device milliseconds per step under ``moe.experts`` in the expert
layers of ``keye_vl_2_0_30b_a3b``: the rows' gather, the masks and the
weights' casts around the three grouped products. XLA's grouped kernels
themselves are traced under no scope (PERF.md section 5): the same gap
as ``moe_experts_ms_per_step`` has."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "moe.experts")
