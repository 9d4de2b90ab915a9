#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the program's main path once, through the entry points a user
calls (``cxxnet_tpu.main.LearnTask`` -> ``Trainer`` -> ``save_model`` ->
``task = serve``'s ``ServeServer``), at the full width of the flagship:
Inception-BN, batch 256, 224x224x3, 1000 classes, bfloat16, ``dev = tpu``.
Every convnet op has one implementation, XLA's own code, so the proof is
a step with no Pallas kernel and no host callback in it. Weights and
data are random, from ``--seed``. ONE process: a chip belongs to one
process at a time, so nothing here starts a child.

    python chip_smoke.py              one chip: train, then serve
    python chip_smoke.py --chips 4    four chips: ONLY the data-parallel
                                      flagship against one device
    python chip_smoke.py --rehearse-cpu [--chips 4]
                                      the same control flow at a tiny
                                      size on the CPU backend — its
                                      last line says "cpu", so it can
                                      never be read as a chip pass

Every phase prints one JSON line (seconds with compile apart from steady
state, peak device bytes, kernel and compile counts); any failed check
exits non-zero. The LAST line of stdout is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without ``--rehearse-cpu`` a platform other than ``tpu`` is a failure:
the script exits non-zero and prints no result. The seconds it prints
are a smoke reading (one run, host clock), not a benchmark.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time
import urllib.request

_REPO = os.path.dirname(os.path.abspath(__file__))

#: the flagship at its published widths, and the rehearsal's toy size
REAL = dict(scale=1.0, image=224, classes=1000, batch=256, steps=8,
            requests=(2, 5), buckets="2,8")
REHEARSAL = dict(scale=0.25, image=64, classes=16, batch=8, steps=4,
                 requests=(2, 3), buckets="2,4")
#: dp=4 vs one device, |difference| of the first step's loss, by compute
#: dtype: float32 is tools/smoke_shard.py's bound; bfloat16 activations
#: round at 2**-8, and the two programs sum the batch statistics in
#: different orders, so single roundings flip — bounded at about one
#: bfloat16 epsilon, fixed before the first chip run
PARITY_BOUND = {"float32": 1e-3, "bfloat16": 5e-3}


class PhaseFailed(Exception):
    """A check of the named phase did not hold."""

    def __init__(self, phase: str, why: str):
        super().__init__(f"{phase}: {why}")
        self.phase = phase


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(ok: bool, phase: str, why: str) -> None:
    if not ok:
        raise PhaseFailed(phase, why)


def flagship_config(size: dict, dev: str, seed: int, model_dir: str,
                    extra=()):
    """The flagship's config pairs: gen_inception_bn's net and globals
    behind one batch of the seeded ``synthetic`` iterator."""
    sys.path.insert(0, os.path.join(_REPO, "examples", "ImageNet"))
    try:
        from gen_inception_bn import generate
    finally:
        sys.path.pop(0)
    from cxxnet_tpu.config import parse_config_string
    shape = f"3,{size['image']},{size['image']}"
    data = ("data = train\niter = synthetic\n"
            f"  num_inst = {size['batch']}\n"
            f"  batch_size = {size['batch']}\n"
            f"  num_class = {size['classes']}\n"
            f"  input_shape = {shape}\n"
            f"  seed_data = {seed}\niter = end\n")
    net = generate(scale=size["scale"], image_size=size["image"],
                   num_class=size["classes"], batch_size=size["batch"],
                   with_data=False)
    return parse_config_string(data + net) + [
        ("dev", dev), ("seed", str(seed)),
        ("model_dir", model_dir)] + list(extra)


def _compiles():
    from cxxnet_tpu.telemetry.anomaly import install_compile_counter
    from cxxnet_tpu.telemetry.registry import REGISTRY
    install_compile_counter()
    return REGISTRY.get("cxxnet_compiles_total")


def _cache_hits() -> int:
    from cxxnet_tpu.telemetry.registry import REGISTRY
    c = REGISTRY.get("cxxnet_compile_cache_hits_total")
    return int(c.value) if c is not None else 0


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _selection(tr) -> dict:
    """{"<kind>:<what>": n} from the trainer's selection log: empty for
    a convnet, whose ops have one implementation each."""
    from cxxnet_tpu.ops.fused import selection_counts
    return {f"{kind}:{what}": n
            for kind, c in selection_counts(tr.net.fused_log).items()
            for what, n in c.items()}


def build_trainer(phase: str, cfg):
    """LearnTask -> initialised Trainer + its one device-resident batch."""
    from cxxnet_tpu.io.data import close_chain
    from cxxnet_tpu.main import LearnTask
    task = LearnTask(cfg)
    tr = task.trainer
    task._init_model()
    it = task.train_iter()
    require(it is not None, phase, "config has no data section")
    try:
        batch = next(iter(it))
        staged = tr.stage_batch(batch)
    finally:
        close_chain(it)
    return task, tr, staged


def take_steps(phase: str, tr, staged, n: int):
    """n updates on the staged batch; each ends in a value fetch of the
    loss. Returns (losses, seconds, compiles seen inside each step)."""
    import math
    counter = _compiles()
    losses, secs, comps = [], [], []
    for _ in range(n):
        c0, t0 = counter.value, time.perf_counter()
        tr.update(staged)
        loss = tr.last_loss                    # float(): the barrier
        secs.append(time.perf_counter() - t0)
        comps.append(int(counter.value - c0))
        losses.append(loss)
        require(math.isfinite(loss), phase, f"non-finite loss {losses}")
    return losses, secs, comps


def train_phase(size, dev, seed, out_dir):
    phase = "train"
    model_dir = os.path.join(out_dir, "models")
    os.makedirs(model_dir, exist_ok=True)
    cfg = flagship_config(size, dev, seed, model_dir)
    t0 = time.perf_counter()
    task, tr, staged = build_trainer(phase, cfg)
    build_s = time.perf_counter() - t0
    losses, secs, comps = take_steps(phase, tr, staged, size["steps"])
    require(losses[-1] < losses[0], phase,
            f"loss did not decrease over the window: {losses}")
    require(sum(comps[1:]) == 0, phase,
            f"compiles after the warm-up step: {comps}")
    sel = _selection(tr)
    text = tr.lower_train_step(staged).as_text()
    kernels = text.count("tpu_custom_call")
    require(not sel and kernels == 0, phase,
            f"a kernel in the convnet's step: {kernels} tpu_custom_call "
            f"in the lowered train step, selection {sel}")
    require("callback" not in text, phase,
            "a host callback in the lowered train step (jit would "
            "not write it to the compile cache)")
    ckpt = tr.checkpoint_path(model_dir, 0)
    tr.save_model(ckpt)
    tr.wait_saves()
    require(os.path.exists(ckpt), phase, f"no checkpoint at {ckpt}")
    say(phase, ok=True, model="inception_bn", batch=size["batch"],
        image=size["image"], classes=size["classes"],
        compute_dtype=tr.policy.compute_name, dev=dev,
        build_s=round(build_s, 3),
        first_step_s=round(secs[0], 3),        # compile + one step
        steady_step_s=round(statistics.median(secs[2:]), 5),
        step_s=[round(s, 5) for s in secs],
        losses=[round(v, 5) for v in losses],
        compiles_per_step=comps, pallas_kernels_in_step=kernels,
        selection=sel, checkpoint=os.path.relpath(ckpt, _REPO),
        checkpoint_bytes=os.path.getsize(ckpt),
        cache_hits=_cache_hits(), peak_bytes_in_use=_peak_bytes())
    return task, tr, cfg, ckpt


def _post(port: int, path: str, payload=None, timeout=600):
    url = f"http://127.0.0.1:{port}{path}"
    body = None if payload is None else json.dumps(payload).encode("utf-8")
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode("utf-8"))


def serve_phase(size, cfg, ckpt, tr, seed):
    """``task = serve`` over the checkpoint just written: two request
    sizes -> two shape buckets, answers checked against
    ``Trainer.predict`` on the same rows, then drain."""
    import numpy as np
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.main import LearnTask
    phase = "serve"
    t0 = time.perf_counter()
    task = LearnTask(cfg + [
        ("task", "serve"), ("model_in", ckpt), ("serve_port", "0"),
        ("serve_buckets", size["buckets"]),
        ("serve_max_batch", size["buckets"].split(",")[-1]),
        ("serve_log_interval", "0")])
    srv = task.build_server()
    srv.start()
    build_s = time.perf_counter() - t0
    c, y, x = tr.graph.input_shape
    rng = np.random.RandomState(seed + 1)
    first_s, again_s, worst = {}, {}, 0.0
    try:
        status, hz = _post(srv.port, "/healthz")
        require(status == 200 and hz.get("ok") is True, phase,
                f"/healthz: {status} {hz}")
        for n in size["requests"]:
            bucket = srv.engine.bucket_for(n)
            # 3 decimals keep the JSON body small; flat rows are in
            # NCHW element order (the engine's request convention)
            rows = np.round(rng.randn(n, c * y * x), 3)
            as_json = rows.tolist()
            t = time.perf_counter()
            status, raw = _post(srv.port, "/predict",
                                {"data": as_json, "raw": 1})
            first_s[bucket] = round(time.perf_counter() - t, 3)
            require(status == 200, phase, f"/predict raw: {status} {raw}")
            prob = np.asarray(raw["prob"], np.float64)
            require(prob.shape == (n, size["classes"])
                    and np.isfinite(prob).all(), phase,
                    f"bad softmax rows: shape {prob.shape}")
            require(np.allclose(prob.sum(axis=1), 1.0, atol=1e-3), phase,
                    f"softmax rows do not sum to 1: {prob.sum(axis=1)}")
            t = time.perf_counter()
            status, ans = _post(srv.port, "/predict", {"data": as_json})
            again_s[bucket] = round(time.perf_counter() - t, 3)
            require(status == 200 and len(ans["pred"]) == n, phase,
                    f"/predict: {status} {ans}")
            # the trainer that wrote the checkpoint, on the same rows at
            # the same padded shape (eval rows are independent)
            pad = np.zeros((bucket, c, y, x), np.float32)
            pad[:n] = rows.astype(np.float32).reshape(n, c, y, x)
            ref_batch = DataBatch(
                data=pad.transpose(0, 2, 3, 1).copy(),
                label=np.zeros((bucket, 1), np.float32),
                num_batch_padd=bucket - n)
            ref = tr.predict_raw(ref_batch)
            diff = float(np.max(np.abs(prob - ref)))
            worst = max(worst, diff)
            require(np.allclose(prob, ref, rtol=5e-2, atol=1e-4), phase,
                    f"served softmax rows differ from Trainer.predict_raw "
                    f"by {diff:.3g} at bucket {bucket}")
            require(list(ans["pred"]) == list(np.argmax(prob, axis=1)),
                    phase, "/predict ids are not the argmax of its own "
                    "softmax rows")
            ref_ids = tr.predict(ref_batch)
            agree = float(np.mean(np.asarray(ans["pred"]) == ref_ids))
            require(agree >= 0.5, phase,
                    f"/predict ids agree with Trainer.predict on only "
                    f"{agree:.0%} of rows at bucket {bucket}")
        status, statz = _post(srv.port, "/statz")
        cells = 2 * len(size["requests"])      # (bucket, raw|predict)
        misses = statz["compile_cache"]["misses"]
        require(misses == cells, phase,
                f"{misses} executables built for {cells} bucket x kind "
                f"cells: {statz['compile_cache']}")
        # a second round of the same shapes must build nothing
        for n in size["requests"]:
            rows = np.round(rng.randn(n, c * y * x), 3)
            status, _ = _post(srv.port, "/predict",
                              {"data": rows.tolist(), "raw": 1})
            require(status == 200, phase, f"repeat /predict: {status}")
        status, statz = _post(srv.port, "/statz")
        require(statz["compile_cache"]["misses"] == cells, phase,
                f"steady-state requests recompiled: "
                f"{statz['compile_cache']}")
        require(statz["requests"]["ok"] >= 3 * len(size["requests"]),
                phase, f"requests not counted ok: {statz['requests']}")
    finally:
        srv.stop()                             # stop accepting + drain
        task.telemetry.close()
    say(phase, ok=True, buckets=sorted(first_s), build_s=round(build_s, 3),
        first_request_s=first_s,               # compile + one answer
        next_request_s=again_s, executables=misses,
        max_abs_diff_vs_trainer=worst, cache_hits=_cache_hits(),
        peak_bytes_in_use=_peak_bytes())


def four_chip_phase(size, kind, seed, out_dir):
    """The data-parallel flagship: the same global batch on ``kind:0-3``
    (sync-BN over the mesh) and on ``kind:0``, one process."""
    phase = "dp4"
    model_dir = os.path.join(out_dir, "models")
    t0 = time.perf_counter()
    task4, tr4, staged4 = build_trainer(phase, flagship_config(
        size, f"{kind}:0-3", seed, model_dir))
    require(tr4.mesh.data_parallel == 4, phase,
            f"mesh is not dp=4: {dict(tr4.mesh.mesh.shape)}")

    def spread(arr):
        return len({s.device for s in arr.addressable_shards})
    import jax
    leaves = jax.tree_util.tree_leaves(tr4.params)
    require(all(spread(a) == 4 for a in leaves), phase,
            "a parameter leaf does not live on four distinct devices")
    require(spread(staged4.data) == 4
            and staged4.data.addressable_shards[0].data.shape[0]
            == size["batch"] // 4, phase,
            "the batch is not split over four distinct devices")
    losses4, secs4, comps4 = take_steps(phase, tr4, staged4, 6)
    require(sum(comps4[1:]) == 0, phase,
            f"compiles after the warm-up step: {comps4}")
    text = tr4.lower_train_step(staged4).compile().as_text()
    require("all-reduce" in text, phase,
            "no all-reduce in the compiled dp=4 step")
    kernels = text.count("tpu_custom_call")
    sel4 = _selection(tr4)
    require(kernels == 0 and not sel4, phase,
            f"{kernels} tpu_custom_call in the compiled dp=4 step, "
            f"selection {sel4}")
    dp4_s = time.perf_counter() - t0
    peak4 = _peak_bytes()
    task4.telemetry.close()
    del task4, tr4, staged4, leaves
    gc.collect()

    t0 = time.perf_counter()
    task1, tr1, staged1 = build_trainer(phase, flagship_config(
        size, f"{kind}:0", seed, model_dir))
    losses1, secs1, _ = take_steps(phase, tr1, staged1, 1)
    task1.telemetry.close()
    d0 = abs(losses4[0] - losses1[0])
    bound = PARITY_BOUND[tr1.policy.compute_name]
    require(d0 < bound, phase,
            f"step-1 loss dp=4 {losses4[0]} vs one device {losses1[0]}: "
            f"|d| = {d0:.3g} >= {bound}")
    say(phase, ok=True, batch=size["batch"], image=size["image"],
        loss_dp4=losses4, loss_one_device=losses1[0],
        step1_loss_diff=d0, bound=bound,
        compute_dtype=tr1.policy.compute_name,
        dp4_first_step_s=round(secs4[0], 3),
        dp4_steady_step_s=round(statistics.median(secs4[2:]), 5),
        dp4_step_s=[round(s, 5) for s in secs4],
        one_device_first_step_s=round(secs1[0], 3),
        all_reduce_in_compiled_step=text.count("all-reduce"),
        pallas_kernels_in_step=kernels, selection=sel4,
        param_leaves_on_four_devices=True, batch_shards=4,
        dp4_phase_s=round(dp4_s, 3),
        one_device_phase_s=round(time.perf_counter() - t0, 3),
        cache_hits=_cache_hits(), peak_bytes_in_use_dp4=peak4,
        peak_bytes_in_use=_peak_bytes())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run the flagship's main path once on the chip")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel flagship against "
                         "one device (default 1: train, then serve)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny size on the CPU backend, kernels "
                         "interpreted; the last line then says cpu")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and requests")
    ap.add_argument("--out", default=os.path.join(_REPO, "chip_smoke_out"),
                    help="where the checkpoint goes (git-ignored)")
    args = ap.parse_args(argv)
    # libtpu would otherwise log under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, _REPO)
    import jax
    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
        # virtual devices for the dp path, unless an embedder (the test
        # suite's 8) already chose at least as many
        if jax.config.jax_num_cpu_devices < args.chips:
            jax.config.update("jax_num_cpu_devices", args.chips)
    import jaxlib
    from importlib import metadata
    from cxxnet_tpu.compile_cache import enable_compile_cache
    devs = jax.devices()
    want = "cpu" if args.rehearse_cpu else "tpu"
    kind = devs[0].platform
    device = {"platform": kind, "kind": devs[0].device_kind,
              "count": len(devs)}
    if kind != want:
        print(f"chip_smoke: JAX found platform {kind!r} "
              f"({devs[0].device_kind} x{len(devs)}), not {want!r}: "
              "nothing was run", file=sys.stderr, flush=True)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr, flush=True)
        return 2
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    cache_dir = enable_compile_cache()
    say("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, cache_dir=cache_dir, chips=args.chips,
        rehearsal=bool(args.rehearse_cpu), seed=args.seed)
    size = REHEARSAL if args.rehearse_cpu else REAL
    os.makedirs(args.out, exist_ok=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chip_phase(size, kind, args.seed, args.out)
        else:
            task, tr, cfg, ckpt = train_phase(
                size, f"{kind}:0", args.seed, args.out)
            task.telemetry.close()
            serve_phase(size, cfg, ckpt, tr, args.seed)
    except PhaseFailed as e:
        say(e.phase, ok=False, error=str(e))
        print(json.dumps({"ok": False, "failed": e.phase,
                          "device": device}), flush=True)
        return 1
    say("total", seconds=round(time.perf_counter() - t0, 3),
        cache_hits=_cache_hits())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
