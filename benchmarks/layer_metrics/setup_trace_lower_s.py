"""Seconds set-up spends tracing functions to jaxprs and lowering them
to MLIR modules, from the PROGRAM's own ``compile.trace`` and
``compile.lower`` spans (its compile instrument, one span per jax
duration event) where a set-up span encloses them before the window
(``benchmarks/setup_reads.py``). Paid on every restart, whatever the
persistent compile cache holds."""

from benchmarks.setup_reads import seconds_in


def read(view):
    return seconds_in(view, "compile.trace", "compile.lower")
