"""Device milliseconds per step under ``mla.attend``: the causal
products q.k and p.v and the softmax between them, of every latent
attention layer, forward, rebuilt forward and backward."""

from benchmarks.joyai_reads import subscope_ms_per_step


def read(view):
    return subscope_ms_per_step(view, "mla.attend")
