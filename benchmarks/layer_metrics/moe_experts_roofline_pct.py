"""Share of the chip's bf16 peak that the held experts' products reach:
the operations of the (position, expert) pairs ACTUALLY held at the last
drained step — the program's ``cxxnet_moe_pairs_held_last_step``, a step
of the traced seconds at the window's end, not the run's mean: the
routing drifts through a window of one repeated batch — through the
reference module's count of one pair, forward once and backward twice,
over the device time a traced step spends under ``moe.experts`` (which
holds the rebuilt forward and the rows' gather too: they lower it, as
they cost)."""

from benchmarks.joyai_reads import configuration, counter, roofline_pct


def read(view):
    pairs = counter("cxxnet_moe_pairs_held_last_step")
    if not pairs:
        return None
    config, ref = configuration()
    return roofline_pct(view, "moe.experts",
                        3.0 * pairs * ref.expert_pair_flops(config))
