"""Device milliseconds per step under the short convolution's scopes
(``shortconv.proj`` and ``shortconv.mix``): the in- and out-projections,
the gates and the causal depthwise convolution of every ``shortconv``
layer, forward, rebuilt forward and backward, first device, per traced
step."""

from benchmarks.lfm2_reads import kind_ms_per_step


def read(view):
    return kind_ms_per_step(view)
