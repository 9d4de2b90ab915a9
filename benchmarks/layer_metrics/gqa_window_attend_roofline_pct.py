"""Share of the chip's bf16 peak that the window layers' attention
products reach: q.k and p.v over the pairs inside the band of every
query head, forward once and backward twice (the reference module's
count), over the device time under ``gqa.attend.window`` (which holds
what the band's tiles multiply outside the band, and the tiles fetched:
they lower it, as they cost)."""

from benchmarks.laguna_reads import attend_roofline_pct


def read(view):
    return attend_roofline_pct(view, "sliding_attention")
