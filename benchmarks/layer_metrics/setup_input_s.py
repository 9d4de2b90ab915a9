"""Seconds of the input's set-up, from the PROGRAM's own
``setup.input`` spans (``io/data.py``: an iterator chain's construction
and the making of its first batch), less any compile inside them
(``benchmarks/setup_reads.py``). The device copy of the batch is not in
them."""

from benchmarks.setup_reads import seconds_in


def read(view):
    return seconds_in(view, "setup.input")
