"""Share of the chip's bf16 peak that the held experts' products reach,
as ``moe_experts_roofline_pct`` counts it: the (position, expert) pairs
held at the last drained step (``cxxnet_moe_pairs_held_last_step``)
through the reference module's count of one pair, forward once and
backward twice, over the device time a traced step spends under
``moe.experts`` (which leaves out the grouped kernels themselves: the
same gap, PERF.md section 5). Count and time are not of the same steps,
as ``laguna_moe_experts_roofline_pct`` says of itself: this router too
has neither a bias nor an auxiliary loss."""

from benchmarks.joyai_reads import counter, roofline_pct
from benchmarks.keye_reads import configuration


def read(view):
    pairs = counter("cxxnet_moe_pairs_held_last_step")
    if not pairs:
        return None
    config, ref = configuration()
    return roofline_pct(view, "moe.experts",
                        3.0 * pairs * ref.expert_pair_flops(config))
