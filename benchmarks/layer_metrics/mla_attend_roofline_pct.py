"""Share of the chip's bf16 peak that the causal attention products
reach: q.k over the joined width and p.v over v's, over the pairs a
causal layer attends, of every latent attention layer, forward once and
backward twice (the reference module's count), over the device time
under ``mla.attend`` (which holds the rebuilt forward and whatever an
implementation multiplies beyond the causal pairs: they lower it)."""

from benchmarks.joyai_reads import configuration, roofline_pct


def read(view):
    config, ref = configuration()
    positions = int(config["input_shape"][-1])
    return roofline_pct(
        view, "mla.attend",
        3.0 * view["rows"] / view["chips"] * ref.attention_layers(config)
        * ref.attention_flops(config, positions))
