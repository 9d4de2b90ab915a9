"""Share of the chip's bf16 peak that the indexer's score products reach:
its heads' q.k over the causal pairs, forward once and backward twice
(the reference module's count), over the device time under ``gqa.index``
(which holds the indexer's projections and the backward's chunked
float32 passes too: they lower it, as they cost)."""

from benchmarks.keye_reads import products_roofline_pct


def read(view):
    return products_roofline_pct(view, "gqa.index", "index_flops")
