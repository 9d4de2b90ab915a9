"""Feed ``records``: JPEG records on disk through the conf's own train
section — ``imgrec`` (sharded read, threaded decode, random crop and
mirror, mean image) behind ``threadbuffer``; the loop's own
``prefetch_device`` stages the batches. The file is cycled as epochs.

The iterator section is the traffic file's ``section`` lines with
``{rec}``, ``{mean}`` and ``{seed}`` filled in, so another mix of the
same kind (other augmentations, another buffer) is another data file.
"""

import os

from benchmarks import records


def section(traffic: dict, ctx: dict) -> str:
    c, y, x = ctx["input_shape"]
    made = records.ensure(
        os.path.join(ctx["cache_dir"], "records"),
        images=int(traffic["images"]), stored=tuple(traffic["stored"]),
        quality=int(traffic["quality"]), classes=int(traffic["classes"]),
        content_seed=int(traffic["content_seed"]), crop=(y, x),
        distinct=int(traffic.get("distinct", 0)))
    ctx["say"](feed="records", wrote=made["wrote"],
               images=int(traffic["images"]),
               mean_encoded_bytes_per_image=made["bytes_per_image"],
               rec=os.path.relpath(made["rec"], ctx["root"]))
    lines = [ln.format(rec=made["rec"], mean=made["mean"], seed=ctx["seed"])
             for ln in traffic["section"]]
    return "data = train\n" + "\n".join(lines) + "\niter = end\n"


class Feed:
    def __init__(self, task, tr, traffic, ctx):
        from cxxnet_tpu.io import native
        self.it = task.train_iter()
        ctx["say"](feed="records", decoder=native.decoder_name(),
                   cpu_count=os.cpu_count())

    def batches(self):
        """Epoch after epoch (each pass reshuffles); the harness ends
        the round."""
        while True:
            n = 0
            for batch in self.it:
                n += 1
                yield batch
            if n == 0:
                raise RuntimeError("the record iterator gave no batch")

    def close(self):
        from cxxnet_tpu.io.data import close_chain
        close_chain(self.it)


def open(task, tr, traffic, ctx):
    return Feed(task, tr, traffic, ctx)
