"""Device milliseconds per step in the forward pass: own time of the
first device's instructions whose scope is under ``jvp(..)`` and not
``transpose(..)`` (``traceparse.classify``: phase ``forward``)."""

from benchmarks.program_reads import scoped_ms_per_step


def read(view):
    return scoped_ms_per_step(view, lambda phase, layer, kind:
                              phase == "forward")
