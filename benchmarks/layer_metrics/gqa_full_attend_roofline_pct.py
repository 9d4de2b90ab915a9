"""Share of the chip's bf16 peak that the full layers' attention
products reach: q.k and p.v over the causal pairs of every query head,
forward once and backward twice (the reference module's count), over
the device time under ``gqa.attend.full`` (which holds the masked halves
of the diagonal's tiles: they lower it, as they cost)."""

from benchmarks.laguna_reads import attend_roofline_pct


def read(view):
    return attend_roofline_pct(view, "full_attention")
