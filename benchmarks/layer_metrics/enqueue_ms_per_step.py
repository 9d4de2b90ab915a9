"""Host milliseconds to enqueue one step, from the PROGRAM's own span:
time in ``train.step_dispatch`` (``Trainer.update`` from entry to the
end of the jitted call; the train-metric drain is outside it, in
``train.metric_drain``) per step of the un-profiled window. The
program's reading of what ``dispatch_ms_per_step`` times from outside."""

from benchmarks.program_reads import span_ms_per_step


def read(view):
    return span_ms_per_step(view, "train.step_dispatch")
