"""Device milliseconds per step under ``moe.route`` in the softmax-scored
no-drop layers of ``keye_vl_2_0_30b_a3b``: the routers' scores, top-k and
the sort of the pairs by expert, forward, rebuilt forward and backward."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "moe.route")
