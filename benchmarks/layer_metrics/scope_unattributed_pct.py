"""Share of the first device's busy time whose instruction has no
scope, or one that ``traceparse.classify`` puts in no phase
(``other``): how much of the step the forward / backward / optimizer
split and the per-kind metrics do not see."""

from benchmarks.program_reads import scoped_seconds


def read(view):
    got = scoped_seconds(view, lambda phase, layer, kind: phase == "other")
    if got is None or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
