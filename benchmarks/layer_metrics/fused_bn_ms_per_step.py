"""Device milliseconds per step under ``fused.bn_act`` alone, forward
and backward: the flagship's 69 conv+BN+relu sites, kernels plus the
re-layouts attributed to them."""

from benchmarks.program_reads import scoped_ms_per_step


def read(view):
    return scoped_ms_per_step(view, lambda phase, layer, kind:
                              kind == "bn_act")
