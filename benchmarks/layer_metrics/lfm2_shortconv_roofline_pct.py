"""Share of the chip's bf16 peak that the short convolutions reach: their
model operations, 2 (4 E^2 + L E) a position a layer (the reference
module's ``shortconv_flops``), forward once and backward twice, over the
device time under every ``shortconv`` scope (which holds the gates, the
convolution and the rebuilt forward too: they lower it, as they
cost)."""

from benchmarks.lfm2_reads import shortconv_roofline_pct


def read(view):
    return shortconv_roofline_pct(view)
