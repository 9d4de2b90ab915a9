#!/usr/bin/env python3
"""The readings ``toy_lm``'s bfloat16 limit was set from, in ONE process
on the chip: for each seed the program's first-step loss on ``chip_lm``
(bfloat16, 8 rows of 2048 positions of the ``synthetic_lm`` rule) beside
the float32 reference's on the same initial weights, and beside the
reference's with the weights off by 1 % and by 10 % (the planted fault
and a coarser one).

    chiprun -- python3 tests/benchmarks/data/chip_lm/readings.py 2000000011 ...

One JSON line a seed, then one of the extremes. Not a run of the
benchmark: no window, no rate."""

import gc
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", "..", "..", ".."))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROWS = 8


def main(seeds):
    import jax
    import numpy as np
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    spec = importlib.util.spec_from_file_location(
        "bench_toy_lm", os.path.join(HERE, "..", "toy", "references",
                                     "toy_lm.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(HERE, "configs", "chip_lm.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "configs", cfg["net"]["conf"])) as f:
        text = f.read()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    vocab, positions = cfg["vocab_size"], cfg["input_shape"][-1]
    sound, off1, off10 = [], [], []
    for seed in seeds:
        seed %= 2 ** 31 - 1
        tr = Trainer(parse_config_string(
            text + f"dev = {dev.platform}:0\nseed = {seed}\n"
            f"batch_size = {ROWS}\nmodel_dir = {ROOT}/benchmarks/.cache/"
            "models\n"))
        tr.init_model()
        rng = np.random.RandomState(seed)
        toks = rng.randint(0, vocab, (ROWS, positions))
        label = (toks + toks[:, :1]) % vocab
        loss = jax.jit(ref.make_loss_fn(tr.graph.layers,
                                        dict(tr.graph.defcfg)))
        p0 = jax.tree_util.tree_map(jax.numpy.copy, tr.params)
        tr.update(DataBatch(
            data=toks.astype(np.float32).reshape(ROWS, 1, 1, positions),
            label=label.astype(np.float32)))
        got = float(tr.last_loss)
        ids, lab = toks.astype(np.int32), label.astype(np.int32)
        want = float(loss(p0, ids, lab))
        by = {s: float(loss(jax.tree_util.tree_map(
            lambda a: a * s, p0), ids, lab)) for s in (1.01, 1.1)}
        sound.append(abs(got - want))
        off1.append(abs(got - by[1.01]))
        off10.append(abs(got - by[1.1]))
        print(json.dumps({"seed": seed, "program": got, "reference": want,
                          "abs_diff": sound[-1], "weights_off_1pct": off1[-1],
                          "weights_off_10pct": off10[-1]}), flush=True)
        del tr, p0, loss
        gc.collect()
    print(json.dumps({"seeds": len(seeds), "sound_max": max(sound),
                      "sound_min": min(sound), "off_1pct_min": min(off1),
                      "off_10pct_min": min(off10)}), flush=True)


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:]])
