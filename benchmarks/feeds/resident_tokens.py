"""Feed ``resident_tokens``: ``resident`` for rows of positions. One
seeded batch of token ids, staged on the device once and handed to the
loop again and again.

The batch comes from the program's ``synthetic_lm`` iterator
(``seed_data = --seed``; its label at a position is the token there plus
the row's first token, modulo the vocabulary) with the configuration
file's ``vocab_size`` and, as the length of a row, the last entry of its
``input_shape`` (``1,1,S``: a flat node of S token ids). It is placed
with ``Trainer.stage_batch`` during set-up. The host-to-device copy is
bypassed: the step does all the work.
"""

# the same Feed: one batch of the conf's train iterator, staged once
from benchmarks.feeds.resident import open  # noqa: F401


def section(traffic: dict, ctx: dict) -> str:
    return ("data = train\niter = synthetic_lm\n"
            f"  num_inst = {ctx['rows']}\n"
            f"  batch_size = {ctx['rows']}\n"
            f"  vocab_size = {int(ctx['config']['vocab_size'])}\n"
            f"  seq_len = {int(ctx['input_shape'][-1])}\n"
            f"  seed_data = {ctx['seed']}\niter = end\n")
