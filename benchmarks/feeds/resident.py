"""Feed ``resident``: one seeded batch, staged on the device once and
handed to the loop again and again.

The batch comes from the program's ``synthetic`` iterator
(``seed_data = --seed``) and is placed with ``Trainer.stage_batch`` —
sharded over the mesh on four chips — during set-up. Decode,
augmentation and the host-to-device copy are bypassed: the step does
all the work.
"""

import itertools


def section(traffic: dict, ctx: dict) -> str:
    c, y, x = ctx["input_shape"]
    return ("data = train\niter = synthetic\n"
            f"  num_inst = {ctx['rows']}\n"
            f"  batch_size = {ctx['rows']}\n"
            f"  num_class = {ctx['num_class']}\n"
            f"  input_shape = {c},{y},{x}\n"
            f"  seed_data = {ctx['seed']}\niter = end\n")


class Feed:
    #: the same batch every step: the harness checks that the loss falls
    one_batch = True

    def __init__(self, task, tr, traffic, ctx):
        from cxxnet_tpu.io.data import close_chain
        it = task.train_iter()
        try:
            self.staged = tr.stage_batch(next(iter(it)))
        finally:
            close_chain(it)

    def batches(self):
        """An endless stream; the harness ends the round."""
        return itertools.repeat(self.staged)

    def close(self):
        self.staged = None


def open(task, tr, traffic, ctx):
    return Feed(task, tr, traffic, ctx)
