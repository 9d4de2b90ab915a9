"""Seconds set-up spends in backend compiles, from the PROGRAM's own
``compile.backend`` spans where a set-up span encloses them before the
window (``benchmarks/setup_reads.py``): an XLA build, or a load from the
persistent compile cache where the span says ``cached``."""

from benchmarks.setup_reads import seconds_in


def read(view):
    return seconds_in(view, "compile.backend")
