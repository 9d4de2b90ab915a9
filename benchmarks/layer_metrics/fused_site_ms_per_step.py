"""Device milliseconds per step under any ``fused.<kind>`` scope: the
Pallas kernels PLUS the reshapes, transposes and copies the compiler
attributes to their wrappers — what the fused sites cost, where
``pallas_ms_per_step`` is the kernels alone and
``relayout_ms_per_step`` every re-layout of the step, theirs or not."""

from benchmarks.program_reads import scoped_ms_per_step


def read(view):
    return scoped_ms_per_step(view, lambda phase, layer, kind: bool(kind))
