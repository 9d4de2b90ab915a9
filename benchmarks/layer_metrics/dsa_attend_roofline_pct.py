"""Share of the chip's bf16 peak that the main attention's products
reach: q.k and p.v over the SELECTED pairs of every head, forward once
and backward twice (the reference module's count), over the device time
under ``gqa.attend.sparse``. Products on pairs that are not selected —
a masked tile is multiplied whole — and the head-summed distribution's
second q.k lower it, as they cost."""

from benchmarks.keye_reads import products_roofline_pct


def read(view):
    return products_roofline_pct(view, "gqa.attend.sparse",
                                 "attention_flops")
