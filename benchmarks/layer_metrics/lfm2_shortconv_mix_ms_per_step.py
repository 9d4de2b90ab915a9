"""Device milliseconds per step under ``shortconv.mix``: the gates and
the causal depthwise convolution (L shifted multiply-adds over the
channels) of every ``shortconv`` layer, and the taps' gradient, forward,
rebuilt forward and backward — bandwidth-bound work beside the
projections' products."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "shortconv.mix")
