#!/usr/bin/env python
"""e2e input-pipeline attribution probes.

Measures, against the attached accelerator:
  1. isolated H2D bandwidth (u8 + f32 batch payloads)
  2. decode+augment+batch throughput (iterator only)
  3. device step time on pre-staged batches (value-synced window)
  4. the contextual-transfer pathology: stage+update interleaved
  5. chained dispatch (update_chain_batches) at k in --chains

Run on a quiet host — concurrent load corrupts the 1-core numbers.
Usage: python tools/e2e_attrib.py [--batch 256] [--steps 8]
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "examples", "ImageNet"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--chains", type=int, nargs="*", default=[4])
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args()

    import numpy as np
    import jax
    from bench import (make_trainer, h2d_bench, decode_bench,
                       _write_synthetic_recordio)
    from cxxnet_tpu.io.data import DataBatch, create_iterator

    print("h2d:", h2d_bench(args.image, args.batch), flush=True)
    dec = decode_bench(image=args.image, n_img=args.steps * 32)
    print("decode:", dec, flush=True)

    tr = make_trainer(args.scale, args.image, 1000, args.batch,
                      jax.devices()[0].platform)
    rng = np.random.RandomState(0)
    mks = [DataBatch(
        data=rng.randint(0, 255, (args.batch, args.image, args.image, 3),
                         np.uint8),
        label=rng.randint(0, 1000, (args.batch, 1)).astype(np.float32),
        norm={"divideby": 255.0}) for _ in range(args.steps)]
    # TWO warm steps: step compile + the post-donation relayout recompile
    tr.update(mks[0])
    float(tr.last_loss)
    tr.update(mks[0])
    float(tr.last_loss)

    staged = [tr.stage_batch(b) for b in mks]
    float(tr.last_loss)
    t0 = time.perf_counter()
    for s in staged:
        tr.update(s)
    float(tr.last_loss)
    n = len(staged)
    print(f"pre-staged updates: {(time.perf_counter()-t0)/n*1e3:.0f} "
          f"ms/step", flush=True)

    t0 = time.perf_counter()
    for b in mks:
        tr.update(b)
    float(tr.last_loss)
    print(f"interleaved stage+update: {(time.perf_counter()-t0)/n*1e3:.0f}"
          f" ms/step", flush=True)

    for k in args.chains:
        tr.update_chain_batches(mks[:k])
        float(tr.last_loss)            # chain compile retires here
        t0 = time.perf_counter()
        done = 0
        for i in range(0, n - n % k, k):
            tr.update_chain_batches(mks[i:i + k])
            done += k
        float(tr.last_loss)
        print(f"chained k={k}: {(time.perf_counter()-t0)/done*1e3:.0f} "
              f"ms/step", flush=True)


if __name__ == "__main__":
    main()
