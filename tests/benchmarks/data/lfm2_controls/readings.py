#!/usr/bin/env python3
"""The readings the bfloat16 limits of ``lfm2_8b_a1b``'s check were set
from, in ONE process on the chip (a step of this configuration takes
minutes to compile, and one trainer re-seeded serves every seed):
for each seed the program's six warm-up steps through ``Trainer.update``
on the cell's batch (1 row of 8192 positions of the ``synthetic_lm``
rule, ids from the vocabulary's slice) and every number
``check("train_steps")`` compares, beside its limit; then, on the first
``--controls`` seeds, each control's reading of the same numbers
(``references/lfm2_8b_a1b_<fault>.py``: the fault planted in the
reference), which has to pass at least one limit.

    python3 tests/benchmarks/data/lfm2_controls/readings.py \\
        --controls 1 2000000011 2000000033 ...      # on the chip

One JSON line a seed and a control, then the extremes. Not a run of the
benchmark: no window, no rate (the seconds a step took are printed for
the reader's eyes and are no benchmark number)."""

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", "..", "..", ".."))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONTROLS = ["float8", "conv_ahead", "no_bias"]


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--controls", type=int, default=1,
                    help="seeds (the first ones) on which every control runs")
    ap.add_argument("--faults", default=",".join(CONTROLS),
                    help="which controls, in this order (a stage a fault "
                    "changes compiles anew: minutes each on the chip)")
    ap.add_argument("--controls-only", action="store_true",
                    help="skip the sound check of the seeds that run "
                    "controls (the cell's own runs have read it)")
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmarks", "configs", "lfm2_8b_a1b.json"))
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()
    import jax
    import numpy as np
    from cxxnet_tpu.compile_cache import enable_compile_cache
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    enable_compile_cache()
    ref = load(os.path.join(ROOT, "benchmarks", "references",
                            "lfm2_8b_a1b.py"), "bench_lfm2_ref")
    with open(args.config) as f:
        cfg = json.load(f)
    with open(os.path.join(os.path.dirname(args.config),
                           cfg["net"]["conf"])) as f:
        text = f.read()
    dev = jax.devices()[0]
    say = lambda **fields: print(json.dumps(fields), flush=True)
    say(platform=dev.platform, kind=dev.device_kind)
    vocab, positions, rows = cfg["vocab_size"], cfg["input_shape"][-1], 1
    tr = Trainer(parse_config_string(
        text + f"dev = {dev.platform}:0\nseed = 0\nbatch_size = {rows}\n"
        f"model_dir = {ROOT}/benchmarks/.cache/models\n"))
    dtype = tr.policy.compute_name
    sound, faults = {}, {}
    for n, seed in enumerate(args.seeds):
        seed %= 2 ** 31 - 1
        # one trainer, re-seeded: free the old weights before the new
        tr.params = tr.opt_state = tr.net_state = None
        tr._pending_metric = None
        tr.seed, tr._base_key = seed, jax.random.PRNGKey(seed)
        tr._rng_key, tr._step_count = None, 0
        tr.epoch_counter = tr.sample_counter = 0
        tr.init_model()
        rng = np.random.RandomState(seed)
        toks = rng.randint(0, vocab, (rows, positions))
        batch = DataBatch(
            data=toks.astype(np.float32).reshape(rows, 1, 1, positions),
            label=((toks + toks[:, :1]) % vocab).astype(np.float32))
        staged = tr.stage_batch(batch)
        losses, t0 = [], time.perf_counter()
        for _ in range(6):
            tr.update(staged)
            losses.append(float(tr.last_loss))
        step_s = (time.perf_counter() - t0) / 6
        view = {"config": cfg, "layers": tr.graph.layers,
                "defaults": dict(tr.graph.defcfg, seed=str(seed)),
                "trainer": tr, "params0": None, "batch0": batch,
                "warm_losses": losses, "dtype": dtype, "rows": rows,
                "chips": 1, "say": say}
        if not (args.controls_only and n < args.controls):
            t0 = time.perf_counter()
            ok, said = ref.check("train_steps", view)
            say(seed=seed, ok=bool(ok), check_s=time.perf_counter() - t0,
                scratch_step_s=step_s, warm_losses=losses, **said)
            for k, v in said.items():
                if k.endswith("_diff"):
                    sound.setdefault(k, []).append(v)
            if n == 0 and said["loss_step2_abs_diff"] > 0.5:
                say(stopped="the sound check is far off: not worth the "
                    "rest")
                return 1
        if n < args.controls:
            for fault in args.faults.split(","):
                mod = load(os.path.join(
                    HERE, "references", f"lfm2_8b_a1b_{fault}.py"),
                    "bench_lfm2_" + fault)
                t0 = time.perf_counter()
                ok_f, said_f = mod.check("train_steps", view)
                diffs = {k: v for k, v in said_f.items()
                         if k.endswith("_diff")}
                say(seed=seed, control=fault, ok=bool(ok_f),
                    check_s=time.perf_counter() - t0,
                    over=[k for k, v in diffs.items()
                          if not v <= said_f[k + "_limit"]], **diffs)
                for k, v in diffs.items():
                    faults.setdefault(fault, {}).setdefault(k, []).append(v)
                del mod
    say(seeds=len(args.seeds),
        sound_max={k: max(v) for k, v in sound.items()},
        sound_min={k: min(v) for k, v in sound.items()},
        control_min={f: {k: min(v) for k, v in by.items()}
                     for f, by in faults.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
