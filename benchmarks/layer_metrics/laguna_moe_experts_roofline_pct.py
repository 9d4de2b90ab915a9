"""Share of the chip's bf16 peak that the held experts' products reach,
as ``moe_experts_roofline_pct`` counts it: the (position, expert) pairs
held at the last drained step (``cxxnet_moe_pairs_held_last_step``)
through the reference module's count of one pair, forward once and
backward twice, over the device time a traced step spends under
``moe.experts`` (which leaves out the grouped kernels themselves: the
same gap, PERF.md section 5).

Count and time are not of the same steps: the time is the mean of the
window's LAST traced steps (8 of them in ``laguna_ep32_train_8k``), the
pairs are the last of those alone, and in that cell the held pairs
still fall by 0.1 to 0.5 % a step there (they double and come half
way back over the window's earlier steps, which the trace does not
cover). By the
record of four seeds the traced steps' mean is 0.9 to 1.3 % over the
last step's pairs, so the share reads LOW by about a hundredth of
itself while the load drifts (PERF.md section 3, PR 32). The registry
keeps no pairs by step; a reader of the traced steps' own pairs needs
them from the program."""

from benchmarks.joyai_reads import counter, roofline_pct
from benchmarks.laguna_reads import configuration


def read(view):
    pairs = counter("cxxnet_moe_pairs_held_last_step")
    if not pairs:
        return None
    config, ref = configuration()
    return roofline_pct(view, "moe.experts",
                        3.0 * pairs * ref.expert_pair_flops(config))
