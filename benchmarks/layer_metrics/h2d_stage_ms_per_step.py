"""Host milliseconds per step in ``train.h2d_stage``
(``Trainer.stage_batch``: shard + ``device_put`` + deferred normalize,
dispatch side). Entered twice a step: from ``prefetch_device`` with the
host batch, and from ``update()`` with the staged one, a pass-through.
About 0 on resident traffic, where the batch is staged once in set-up."""

from benchmarks.program_reads import span_ms_per_step


def read(view):
    return span_ms_per_step(view, "train.h2d_stage")
