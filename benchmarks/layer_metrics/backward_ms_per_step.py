"""Device milliseconds per step in the backward pass: own time of the
first device's instructions whose scope is under ``transpose(jvp(..))``
(``traceparse.classify``: phase ``backward``)."""

from benchmarks.program_reads import scoped_ms_per_step


def read(view):
    return scoped_ms_per_step(view, lambda phase, layer, kind:
                              phase == "backward")
