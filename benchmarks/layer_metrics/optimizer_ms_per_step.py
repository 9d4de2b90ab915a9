"""Device milliseconds per step in the parameter update: own time of
the first device's instructions under the step builders' ``optimizer``
scope (the fused ``sgd_apply`` pack and kernel included)."""

from benchmarks.program_reads import scoped_ms_per_step


def read(view):
    return scoped_ms_per_step(view, lambda phase, layer, kind:
                              phase == "optimizer")
