"""Device milliseconds per step under ``head_loss``: the head's
log-softmax over the vocabulary slice, its loss and the train metric's
reduction on the device."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "head_loss")
