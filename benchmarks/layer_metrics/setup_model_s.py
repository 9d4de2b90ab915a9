"""Seconds of ``LearnTask`` and its weights, from the PROGRAM's own
``setup.task`` (``LearnTask.__init__``: config, session, compile cache,
the ``Trainer`` with its graph and mesh) and ``setup.weights``
(``LearnTask._init_model``: the jitted initialiser, the optimizer state,
a restore) spans, less the compiles inside them
(``benchmarks/setup_reads.py``)."""

from benchmarks.setup_reads import seconds_in


def read(view):
    return seconds_in(view, "setup.task", "setup.weights")
