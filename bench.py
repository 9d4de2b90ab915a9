#!/usr/bin/env python
"""Benchmark: Inception-BN training — MFU-grounded and self-verifying.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
     "model_tflops": ..., "mfu_pct": ..., "mfu_est": ...,
     "achieved_flops": ..., "compute_dtype": "bfloat16", "roofline_pct":
     ..., "arith_intensity": ..., "e2e_images_per_sec_per_chip": ...,
     "fp32_compare": {...,"speedup_vs_f32": N}, "loss_start": ...,
     "loss_end": ...}

Every phase (flagship compute, e2e, secondary models, the fp32 rerun)
reports achieved FLOP/s + an MFU estimate and is tagged with its compute
dtype, so the bf16-vs-fp32 speedup lands in the metric trajectory as a
measured ratio (``fp32_compare.speedup_vs_f32``), not an anecdote. FLOPs
come from the compiled executable's cost analysis, falling back to an
analytic conv/matmul count on backends that report none.

Three claims, each verified in-run:
  * throughput  — images/sec/chip of the real train step (forward +
    backward + SGD, bf16 compute) on device-resident batches, timed as the
    slope between two k-step chained dispatches (Trainer.update_chain) so
    the number is pure device time — per-dispatch wall timing of a small
    step measures the host's dispatch path, not the chip.
  * efficiency  — step FLOPs come from XLA's compiled-executable cost
    analysis (Trainer.step_cost_analysis), turned into sustained TFLOP/s
    and MFU against the detected chip's bf16 peak. This is the analog of
    the reference's health bar "GPU utilization normally above 95%"
    (/root/reference/doc/debug_perf.md:3-5); a raw ratio against 2015
    hardware is reported only as ``vs_baseline`` context.
  * correctness — the bench asserts the training loss strictly decreased
    over the timed window (the step must be *learning*, not just fast).

Additionally reports an end-to-end input-pipeline number: JPEG records on
disk -> sharded read -> decode -> augment (rand crop+mirror) -> host->device
-> train step, in images/sec/chip — the path the reference's whole threaded
IO design optimizes (SURVEY §7).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "examples", "ImageNet"))

# Context anchor only (reference-class 2015 GPU throughput for Inception-BN,
# the rigs behind example/ImageNet/Inception-BN.conf's published runs).
# Efficiency claims are grounded in MFU below, not in this constant.
BASELINE_IPS = 150.0

# (dense bf16 peak TFLOP/s, HBM GB/s) per chip, by device_kind substring.
# First match in list order wins — keep more specific keys (v5p, v5 lite)
# before their prefixes (v5). Sources: public TPU spec sheets.
_CHIP_PEAKS = [
    ("v6", (918.0, 1638.0)), ("v5p", (459.0, 2765.0)),
    ("v5 lite", (197.0, 819.0)), ("v5e", (197.0, 819.0)),
    ("v5", (459.0, 2765.0)), ("v4", (275.0, 1228.0)),
    ("v3", (123.0, 900.0)), ("v2", (45.0, 700.0)),
]


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def chip_peaks(device):
    """(dense bf16 peak TFLOP/s, HBM GB/s) of ``device`` — or ``None`` on
    the CPU backend, where the test suite calls these helpers and MFU /
    roofline are not measured. An accelerator whose ``device_kind`` is
    not in the table is an error, never a default."""
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    for key, peaks in _CHIP_PEAKS:
        if key in kind:
            return peaks
    raise ValueError(
        f"no peak FLOP/s and bandwidth on record for device kind "
        f"{device.device_kind!r}: add it to _CHIP_PEAKS, with its source")


def _on_tpu() -> bool:
    """A failed phase may become a field of the JSON line only where
    no chip number is at stake (the CPU-pinned helper tests); on a TPU
    backend it re-raises — a benchmark that exits 0 over a broken
    phase hides the failure it exists to show."""
    import jax
    return jax.default_backend() == "tpu"


# bench trainers default telemetry OFF (the step-time
# probe syncs the loss every telemetry_sync_interval steps and its
# accounting rides every update() — timed paths must not pay for
# diagnostics). Caller overrides still win (last occurrence rules).
_BENCH_DEFAULTS = (("telemetry_steptime", "0"),)


def make_trainer(scale, image, classes, batch, platform, overrides=()):
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.trainer import Trainer
    from gen_inception_bn import generate
    txt = generate(scale=scale, image_size=image, num_class=classes,
                   batch_size=batch, with_data=False)
    cfg = parse_config_string(txt) + [("eval_train", "0"),
                                      ("dev", platform)] \
        + list(_BENCH_DEFAULTS) + list(overrides)
    tr = Trainer(cfg)
    tr.init_model()
    return tr


def dtype_name(tr) -> str:
    """The trainer's compute dtype as a JSON-friendly tag ('float32' /
    'bfloat16' / 'float16') — every emitted metric carries it so a
    bf16-vs-f32 speedup reads out of the metric trajectory as a ratio
    of like-tagged numbers, not an anecdote."""
    return tr.policy.compute_name


def analytic_step_flops(tr, batch) -> float:
    """Analytic conv/matmul FLOP count for ONE train step — the fallback
    when the backend's compiled cost_analysis reports no 'flops' key
    (observed on the CPU backend). Forward matmul/conv work is
    2*M*N*K; the backward pass recomputes ~2x that (dX and dW), so the
    train step is ~3x forward. MXU-dominant layers only (conv, fullc,
    seqfc, ffn, mha) — elementwise/norm traffic is bandwidth, not FLOPs,
    at the roofline scales this grounds."""
    total = 0.0
    g, net = tr.graph, tr.net
    for li, (spec, layer) in enumerate(zip(g.layers, net.layers)):
        t = (g.layers[spec.primary_layer_index].type if spec.is_shared
             else spec.type)
        in_sh = net._in_shapes_of[li]
        out_sh = net.layer_out_shapes[li]
        if t == "conv":
            cout, oy, ox = out_sh[0]
            hp = layer.hp
            total += 2.0 * batch * oy * ox * hp.kernel_height * \
                hp.kernel_width * (layer._cin // hp.num_group) * cout
        elif t == "fullc":
            total += 2.0 * batch * layer._in_num * layer.hp.num_hidden
        elif t == "seqfc":
            e, s, _ = in_sh[0]
            total += 2.0 * batch * s * e * layer.hp.num_hidden
        elif t == "ffn":
            e, s, _ = in_sh[0]
            f = layer.hp.num_hidden or 4 * e
            total += 2.0 * 2.0 * batch * s * e * f
        elif t == "mha":
            e, s, _ = in_sh[0]
            total += 4.0 * 2.0 * batch * s * e * e   # q/k/v/o projections
            total += 2.0 * 2.0 * batch * s * s * e   # qk^T and pv
    return 3.0 * total


def analytic_step_bytes(tr, batch) -> dict:
    """doc/bytes_audit.md-style analytic HBM byte model of ONE train
    step — the calibration fallback for backends whose profiler trace
    records no memory counters. Model: every layer's forward reads its
    inputs and writes its outputs once; the backward re-reads the saved
    activation and the cotangent and writes dx (~2x forward), so
    activation traffic ~= 3 * (in + out) per layer in the compute
    dtype; params pay ~5 fp32 passes (read p/m, write p/m, grad). A
    fusion-blind upper-estimate by construction — same epistemic status
    as cost_analysis' pre-fusion bytes, derived independently."""
    import jax
    import numpy as np
    g, net = tr.graph, tr.net
    esize = np.dtype(net.compute_dtype).itemsize
    act = 0.0
    for li, spec in enumerate(g.layers):
        ins = sum(float(np.prod(s)) for s in net._in_shapes_of[li])
        outs = sum(float(np.prod(s)) for s in net.layer_out_shapes[li])
        act += 3.0 * batch * (ins + outs) * esize
    n_params = sum(leaf.size
                   for leaf in jax.tree_util.tree_leaves(tr.params))
    params = 5.0 * 4 * n_params
    return {"activation_bytes": act, "param_bytes": params,
            "total": act + params}


def calibration_entry(cost_bytes: float, measured_bytes,
                      analytic_bytes: float) -> dict:
    """The calibrated-roofline record: measured (trace) HBM bytes per
    step vs the cost_analysis estimate every BENCH round has carried.
    ``measured_vs_cost_ratio`` is THE calibration number — <1 means XLA
    fused below its own pre-fusion estimate (roofline_pct > 100
    readings were real); None means the trace had no memory counters
    and the analytic model is the only cross-check."""
    measured = measured_bytes if measured_bytes else None
    return {
        "cost_analysis_bytes_per_step": round(cost_bytes, 1),
        "measured_bytes_per_step": (round(measured, 1)
                                    if measured else None),
        "analytic_bytes_per_step": round(analytic_bytes, 1),
        "measured_vs_cost_ratio": (round(measured / cost_bytes, 4)
                                   if measured and cost_bytes else None),
        "analytic_vs_cost_ratio": (round(analytic_bytes / cost_bytes, 4)
                                   if cost_bytes else None),
        "hbm_bytes_per_step_calibrated": round(measured or cost_bytes, 1),
        "source": ("trace" if measured else
                   "cost_analysis (trace lacked memory counters; "
                   "analytic model is the only independent check)"),
    }


def profile_attribution(tr, classes, batch, k=8):
    """Capture a jax.profiler trace of ``k`` flagship steps and
    attribute device op time to forward / backward / optimizer
    through the step's own scopes — telemetry.traceparse. The step is
    warmed (compile retired) BEFORE the bracket so the trace holds
    steady-state steps only. Returns the attribution dict
    (JSON-rounded); off the TPU there is no chip's plane to read and
    the result is an {"error": ...} marker."""
    import numpy as np
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.telemetry.profiler import device_trace
    from cxxnet_tpu.telemetry.traceparse import attribute_profile
    try:
        c_in, y_in, x_in = tr.graph.input_shape
        rng = np.random.RandomState(1)
        b = DataBatch(
            data=rng.rand(batch, y_in, x_in, c_in).astype(np.float32),
            label=rng.randint(0, classes,
                              size=(batch, 1)).astype(np.float32))
        b.data = tr.mesh.shard_batch(b.data)
        b.label = tr.mesh.shard_batch(b.label)
        for _ in range(3):                    # compile + warm, untraced
            tr.update(b)
        float(tr.last_loss)
        dump = tempfile.mkdtemp(prefix="bench_profile_")
        # device_trace: host and python tracers OFF — either floods the
        # profiler's event cap and evicts the op events the attribution
        # exists to read. Plain update() calls, not a chain: one module
        # run per step is what the reader counts steps by, and the step
        # update() ran is the one whose scopes the program describes
        with device_trace(dump) as clock:
            for _ in range(k + 2):
                tr.update(b)
            float(tr.last_loss)               # value sync inside bracket
        att = attribute_profile(dump, clock=clock)
        if att is None:
            raise ValueError("the dump holds no chip's plane, or fewer "
                             "than three runs of the step")
    except Exception as e:
        if _on_tpu():
            raise
        return {"error": f"{type(e).__name__}: {e}"}
    r4 = lambda v: round(v, 4)
    att["phases"] = {
        ph: {"ms": r4(d["ms"]), "pct": round(d["pct"], 2),
             "count": d["count"]}
        for ph, d in sorted(att["phases"].items(),
                            key=lambda kv: -kv[1]["ms"])}
    att["kinds"] = {ph: {k_: r4(v) for k_, v in ks.items()}
                    for ph, ks in att["kinds"].items()}
    for key in ("layers", "top_unattributed", "idle_gaps"):
        att[key] = [(n, r4(ms)) for n, ms in att[key]]
    for key in ("step_ms", "busy_ms", "idle_pct", "unattributed_pct"):
        att[key] = r4(att[key])
    att["dump_dir"] = dump
    return att


def input_fold_entry(tr, c, image, classes, batch) -> dict:
    """Price the input_fold second-wave optimization in the same
    artifact: cost-analysis bytes of the FOLDED step (uint8 batch +
    in-step normalize) vs the f32-input step the headline number times,
    PLUS the eager normalize dispatch the fold deletes (u8 read + f32
    write + the step's f32 re-read = 9 bytes/px vs the fold's 1+2).
    Bytes evidence, not a timing claim — the measured carrier is
    e2e_u8, whose production path folds for real."""
    import numpy as np
    from cxxnet_tpu.io.data import DataBatch
    try:
        rng = np.random.RandomState(2)
        u8 = rng.randint(0, 256, (batch, image, image, 3), np.uint8)
        lab = rng.randint(0, classes, size=(batch, 1)).astype(np.float32)
        b = DataBatch(data=u8, label=lab,
                      norm={"mean": np.asarray([123.0, 117.0, 104.0],
                                               np.float32),
                            "divideby": 255.0, "scale": 1.0})
        folded = tr._fold_capable(b)
        cost = tr.step_cost_analysis(b)
        in_bytes = float(u8.size)
        eager_extra = in_bytes * (1 + 4)   # u8 read + f32 write, eager
        f32_step = c["hbm_bytes_per_step"]
        return {
            "active": bool(folded),
            "step_bytes_folded": round(cost["bytes_accessed"], 1),
            "step_bytes_f32_input": round(f32_step, 1),
            "eager_normalize_extra_bytes": round(eager_extra, 1),
            "bytes_saved_per_step": round(
                f32_step + eager_extra - cost["bytes_accessed"], 1),
        }
    except Exception as e:
        if _on_tpu():
            raise
        return {"error": f"{type(e).__name__}: {e}"}


def make_conf_trainer(conf_rel, batch, platform, overrides=()):
    """Trainer from a shipped example conf's net/global sections (data
    sections dropped — the bench feeds device-resident batches)."""
    from cxxnet_tpu.config import parse_config_file
    from cxxnet_tpu.main import split_sections
    from cxxnet_tpu.trainer import Trainer
    cfg = parse_config_file(os.path.join(_REPO, conf_rel))
    global_cfg, _ = split_sections(cfg)
    cfg = global_cfg + [("batch_size", str(batch)), ("eval_train", "0"),
                        ("dev", platform)] \
        + list(_BENCH_DEFAULTS) + list(overrides)
    tr = Trainer(cfg)
    tr.init_model()
    return tr


def single_chip_cost(build_trainer, batch_per_chip, classes):
    """Per-chip cost truth for multi-chip runs: lower the SAME train step
    on one device at the per-chip batch and read its compiled cost
    analysis — deterministic, unlike inferring whether a multi-chip
    cost_analysis() reported per-device or whole-module numbers.
    ``build_trainer(batch)`` must build on a single-device mesh."""
    import numpy as np
    from cxxnet_tpu.io.data import DataBatch
    tr = build_trainer(batch_per_chip)
    c_in, y_in, x_in = tr.graph.input_shape
    rng = np.random.RandomState(0)
    b = DataBatch(
        data=rng.rand(batch_per_chip, y_in, x_in, c_in).astype(np.float32),
        label=rng.randint(0, classes,
                          size=(batch_per_chip, 1)).astype(np.float32))
    b.data = tr.mesh.shard_batch(b.data)
    b.label = tr.mesh.shard_batch(b.label)
    return tr.step_cost_analysis(b)


def compute_bench(tr, image, classes, batch, steps, ref_cost_fn=None):
    """Device-resident compute-path timing + cost analysis + loss check.

    Timing method: k train steps chained in ONE dispatch
    (Trainer.update_chain, a lax.scan over the step body) at two chain
    lengths, per-step time = the slope between them. Per-dispatch wall
    timing is wrong on BOTH sides: a tiny model measures the host's
    dispatch path (≫ its device time), and a one-off layout-churn
    recompile landing inside the timed window inflates a step many times
    over. The slope cancels every fixed cost (dispatch, sync, fetch);
    warming both chain lengths first retires the compiles. ``ref_cost_fn`` (multi-chip runs): returns the single-chip
    cost dict used as per-chip truth for the MFU/roofline math."""
    import jax
    import numpy as np
    from cxxnet_tpu.io.data import DataBatch

    c_in, y_in, x_in = tr.graph.input_shape
    rng = np.random.RandomState(0)
    b = DataBatch(
        data=rng.rand(batch, y_in, x_in, c_in).astype(np.float32),
        label=rng.randint(0, classes, size=(batch, 1)).astype(np.float32))
    b.data = tr.mesh.shard_batch(b.data)
    b.label = tr.mesh.shard_batch(b.label)   # device-resident: time compute

    cost = tr.step_cost_analysis(b)          # compiles once (cache-shared)
    # FLOPs ground truth: XLA's compiled cost analysis, falling back to
    # the analytic conv/matmul count when the backend reports none — the
    # step FLOP count must exist on every backend
    flops_source = "cost_analysis"
    if not cost.get("flops"):
        cost = dict(cost, flops=analytic_step_flops(tr, batch))
        flops_source = "analytic"
    # probe chain: estimate the per-step time, then size K2 for a ~1.5-3 s
    # timed chain so the K2-K1 difference dwarfs host jitter. The FIRST
    # probe call pays the scan's jit compile, which
    # would dwarf the step time and clamp K2 to its minimum — estimate
    # from a SECOND, post-compile call
    timing_method = "chained"
    try:
        probe_k = max(2, min(8, steps))
        first_losses = tr.update_chain(b, probe_k)
        loss_start = float(first_losses[0])
        # size the timed chains from a geometric probe ladder: quadruple
        # k until one chain's wall time clearly exceeds the dispatch+
        # fetch floor, then estimate the per-step time from the LAST TWO
        # rungs' slope. A single probe divided by k inflates the
        # estimate by floor/k and shrinks the window below the jitter
        # floor for sub-ms models (a floor-sized window makes the slope
        # sign-flip on jitter).
        k_prev, t_prev = probe_k, min(
            _timed(lambda: float(tr.update_chain(b, probe_k)[-1]))
            for _ in range(2))
        k_cur, t_cur = k_prev, t_prev
        while t_cur < 0.8 and k_cur < 4096:
            k_prev, t_prev = k_cur, t_cur
            k_cur = k_cur * 4
            float(tr.update_chain(b, k_cur)[-1])         # compile + warm
            t_cur = min(
                _timed(lambda: float(tr.update_chain(b, k_cur)[-1]))
                for _ in range(2))
        if k_cur == k_prev:
            # ladder never iterated: the first probe already exceeded the
            # floor (slow model, >=100 ms/step) — the fixed cost is
            # negligible there, a plain per-step division is accurate
            est = max(t_cur / k_cur, 1e-5)
        else:
            est = max((t_cur - t_prev) / (k_cur - k_prev), 1e-5)
        k2 = int(max(8, min(6000, 2.0 / est)))
        loss_end = None
        for attempt in range(2):
            k1 = max(2, k2 // 8)
            # warm both chain lengths (compile + donation layout settle)
            float(tr.update_chain(b, k1)[-1])
            float(tr.update_chain(b, k2)[-1])
            times = {k1: [], k2: []}
            for k in (k1, k2, k1, k2, k1, k2):
                t0 = time.perf_counter()
                losses = tr.update_chain(b, k)
                loss_end = float(losses[-1])  # value sync ends the timing
                times[k].append(time.perf_counter() - t0)
            dt_step = (min(times[k2]) - min(times[k1])) / (k2 - k1)
            if dt_step > 0:
                break
            # jitter swamped the window: one retry with a 2x chain
            k2 = min(12000, k2 * 2)
        if dt_step <= 0:                     # jitter swamped a tiny model
            raise RuntimeError(
                f"non-positive slope ({dt_step:.2e}s) — host jitter "
                f"exceeded the k2-k1 window")
    except Exception as e:
        if _on_tpu():
            raise
        # off the TPU (helper tests): fall back to per-dispatch wall
        # timing (overstates step time by the dispatch cost — flagged in
        # the output)
        print(f"chained timing unavailable ({type(e).__name__}: {e}); "
              f"falling back to per-dispatch wall timing", file=sys.stderr)
        timing_method = f"per-dispatch wall fallback ({type(e).__name__})"
        # re-init: a failed chain may have (a) consumed the donated
        # param/opt buffers mid-execution and (b) already driven the
        # fixed-batch loss to its floor, which would void the
        # loss-decrease self-check below
        tr.init_model()
        tr.update(b)
        tr.update(b)
        loss_start = tr.last_loss
        t0 = time.perf_counter()
        for _ in range(steps):
            tr.update(b)
        loss_end = float(tr._last_loss)      # value sync (see note above)
        dt_step = (time.perf_counter() - t0) / steps

    assert loss_end < loss_start, (
        f"bench self-check failed: loss did not decrease over the timed "
        f"window ({loss_start:.4f} -> {loss_end:.4f}); the step is not "
        f"learning, so the throughput number is void")

    n_chips = max(1, tr.mesh.num_devices)
    ips = batch / dt_step / n_chips
    # compiled cost_analysis reports the per-device (SPMD-partitioned)
    # module's FLOPs on the validated single-chip setup; some XLA versions
    # report whole-module FLOPs on a multi-chip mesh, which would inflate
    # mfu/roofline by n_chips. Guard: per-chip sustained throughput above
    # the chip's physical bf16 peak is impossible — treat that as a
    # whole-module report and divide by n_chips (flagged in the output).
    # None on the CPU backend: MFU / roofline are then not measured
    peak, hbm_gbs = chip_peaks(jax.devices()[0]) or (None, None)
    flops = cost["flops"]
    flops_normalized = False
    if n_chips > 1:
        ref = None
        if ref_cost_fn is not None:
            try:
                ref = ref_cost_fn()
            except Exception as e:         # fall through to the peak clip
                print(f"single-chip cost probe failed: {e}",
                      file=sys.stderr)
        if ref is not None and ref.get("flops"):
            # whole-module reports show up as ~n_chips x the 1-chip truth;
            # either way the 1-chip numbers ARE the per-chip cost
            flops_normalized = cost["flops"] > 1.5 * ref["flops"]
            cost = dict(cost, flops=ref["flops"],
                        bytes_accessed=ref["bytes_accessed"])
            flops = cost["flops"]
    sustained_tflops = flops / dt_step / 1e12
    if n_chips > 1 and peak and sustained_tflops > 1.05 * peak:
        # last-resort heuristic when the 1-chip probe was unavailable:
        # per-chip sustained above physical peak must be a whole-module
        # report (bytes from the same report: divide both)
        flops = flops / n_chips
        sustained_tflops = flops / dt_step / 1e12
        flops_normalized = True
        cost = dict(cost, bytes_accessed=cost["bytes_accessed"] / n_chips)
    cost = dict(cost, flops=flops)
    # roofline: with arithmetic intensity AI = flops/byte, the achievable
    # rate is min(MXU peak, AI * HBM bandwidth). Inception-BN at batch 256
    # is HBM-bound (AI ~ 64 flop/byte on v5e), so roofline_pct — not raw
    # MFU — is the analog of the reference's "GPU utilization normally
    # above 95%" health bar (/root/reference/doc/debug_perf.md:3-5).
    have_bytes = cost["bytes_accessed"] > 0
    ai = cost["flops"] / cost["bytes_accessed"] if have_bytes else 0.0
    achievable = (min(peak, ai * hbm_gbs / 1e3)
                  if peak and have_bytes else None)
    roofline_pct = (100.0 * sustained_tflops / achievable
                    if achievable else None)
    mfu = 100.0 * sustained_tflops / peak if peak else None
    return {
        "ips": ips,
        "per_step_ms": dt_step * 1e3,
        "step_tflop": cost["flops"] / 1e12,
        "model_tflops": sustained_tflops,
        # achieved FLOP/s per chip (raw, not TFLOP-scaled) and the MFU
        # estimate against the chip's dense bf16 peak — the per-phase
        # pair every bench section reports; mfu_est is None (not
        # measured) on the CPU backend
        "achieved_flops": flops / dt_step,
        "mfu_est": mfu,
        "flops_source": flops_source,
        "compute_dtype": dtype_name(tr),
        # mfu_pct: legacy alias of mfu_est for compute phases (kept so
        # earlier trajectory entries keep comparing); the e2e phase is
        # the one place mfu_est is a distinct (ips-derived) quantity
        "mfu_pct": mfu,
        # >100 is possible and fine: cost_analysis bytes are pre-fusion
        # (every intermediate counted); when XLA fuses intermediates away
        # the true arithmetic intensity exceeds the estimate, so the
        # bytes-implied cap is conservative, not a law of physics
        "roofline_pct": roofline_pct,
        "arith_intensity": ai,
        # compiled-step HBM traffic (cost_analysis bytes-accessed): the
        # flagship is bandwidth-bound, so a byte saving must show here
        # (and as a higher arith_intensity), not be asserted
        "hbm_bytes_per_step": cost["bytes_accessed"],
        "peak_bf16_tflops": peak,
        "hbm_gbs": hbm_gbs,
        "loss_start": loss_start,
        "loss_end": loss_end,
        "n_chips": n_chips,
        "flops_normalized": flops_normalized,
        "timing_method": timing_method,
    }


def _write_synthetic_recordio(path, n, src_size, classes, seed=0):
    """Pack n JPEG-encoded smooth random images (realistic compressibility,
    unlike noise) into our recordio format."""
    import numpy as np
    from cxxnet_tpu.io.recordio import ImageRecord, RecordWriter

    try:
        import cv2
        def encode(img):
            ok, buf = cv2.imencode(".jpg", img[:, :, ::-1])
            assert ok
            return buf.tobytes()
    except ImportError:
        import io as _io
        from PIL import Image
        def encode(img):
            b = _io.BytesIO()
            Image.fromarray(img).save(b, "JPEG")
            return b.getvalue()

    rng = np.random.RandomState(seed)
    with RecordWriter(path) as w:
        for i in range(n):
            lo = rng.randint(0, 256, size=(8, 8, 3), dtype=np.uint8)
            img = np.kron(lo, np.ones((src_size // 8, src_size // 8, 1),
                                      np.uint8))
            w.write(ImageRecord(
                inst_id=i, labels=np.asarray([i % classes], np.float32),
                data=encode(img)).pack())


def e2e_bench(tr, image, classes, batch, steps, device_normalize=0,
              chain=4):
    """End-to-end images/sec/chip: recordio on disk -> sharded read ->
    threaded JPEG decode -> augment (rand crop+mirror) -> H2D -> train
    step. Covers the data plane the compute bench deliberately excludes.
    ``device_normalize=1`` ships uint8 batches (4x smaller H2D) and
    normalizes on-device — the recommended production input path.

    Dispatch: ``chain`` host batches stack into ONE H2D put + one fused
    k-step dispatch (Trainer.update_chain_batches — the task driver's
    ``train_chain`` production path), coalescing the transfers at chain
    boundaries. ``chain=0`` falls back to per-batch update().

    Timing: slope between an n1-batch and an n2-batch window, each
    ended by a true value sync — cancels pipeline fill, iterator
    restart, and the final fetch. Returns (ips, detail_dict)."""
    import numpy as np
    from cxxnet_tpu.io.data import DataBatch, create_iterator

    n_img = steps * batch
    with tempfile.TemporaryDirectory() as td:
        rec = os.path.join(td, "bench.rec")
        _write_synthetic_recordio(rec, n_img, src_size=image + 32,
                                  classes=classes)
        cfg = [
            ("iter", "imgrec"),
            ("image_rec", rec),
            ("input_shape", f"3,{image},{image}"),
            ("batch_size", str(batch)),
            ("rand_crop", "1"),
            ("rand_mirror", "1"),
            ("shuffle", "1"),
            ("device_normalize", str(device_normalize)),
            ("iter", "threadbuffer"),
            ("iter", "end"),
        ]
        it = create_iterator(cfg)

        def copy(b):
            # iterators may refill their buffers under the chain queue
            return DataBatch(data=np.array(b.data),
                             label=np.array(b.label),
                             num_batch_padd=b.num_batch_padd, norm=b.norm)

        def window(n_batches):
            """Consume n_batches through the train path; wall time to a
            true value sync (a value fetch is the barrier that cannot
            return early). With chaining, only whole
            dispatched chains are timed AND counted: a leftover partial
            chain (iterator exhausted mid-chain) is dropped after the
            sync instead of flushed through per-batch update() inside
            the window — the first such flush would compile the
            non-chain train step and skew that window's slope
            (ADVICE r5)."""
            t0 = time.perf_counter()
            count, pend = 0, []
            # chain=0 keeps device-side double buffering (H2D of
            # batch N+1 staged while step N computes)
            src = it if chain else tr.prefetch_device(it)
            for b in src:
                if chain:
                    pend.append(copy(b))
                    if len(pend) < chain:
                        continue
                    rows = sum(x.batch_size - x.num_batch_padd
                               for x in pend)
                    tr.update_chain_batches(pend)
                    pend = []
                    count += rows
                else:
                    tr.update(b)
                    count += b.batch_size - b.num_batch_padd
                if count >= n_batches * batch:
                    break
            float(tr.last_loss)
            return time.perf_counter() - t0, count

        # warm pass: page cache, decode pool, chain compile, and the
        # post-donation relayout recompile all retire here. Off the TPU
        # a chain failure falls back to per-batch dispatch (recorded as
        # chain_fallback in the detail dict). A failed chain may have
        # consumed the donated param/opt buffers mid-execution, so
        # re-init before retrying (same recovery as compute_bench's).
        chain_fallback = False
        try:
            window(min(steps, 2 * max(chain, 1)))
        except Exception as e:
            if not chain or _on_tpu():
                raise
            print(f"e2e chain dispatch unavailable "
                  f"({type(e).__name__}: {e}); falling back to "
                  f"per-batch update", file=sys.stderr)
            chain = 0
            chain_fallback = True
            tr.init_model()
            window(min(steps, 2))
        n2 = steps
        n1 = max(chain, steps // 3)
        if chain:                      # windows = whole chains
            n1, n2 = (max(chain, n1 // chain * chain),
                      max(2 * chain, n2 // chain * chain))
        t1, c1 = window(n1)
        t2, c2 = window(n2)
        if c2 > c1 and t2 > t1:
            ips_raw = (c2 - c1) / (t2 - t1)
            timing = (f"window slope ({n1} vs {n2} batches, "
                      f"value-synced)")
        else:                          # degenerate window (tiny corpus)
            ips_raw = c2 / t2
            timing = (f"single {c2}-image window, value-synced "
                      f"(corpus too small for distinct slope windows)")
    n_chips = max(1, tr.mesh.num_devices)
    detail = {
        "dispatch": (f"update_chain_batches k={chain}" if chain
                     else "per-batch update (prefetch double-buffered)"),
        "timing": timing,
        "compute_dtype": dtype_name(tr),
        # uint8 windows (device_normalize=1) ride the input_fold when
        # the trainer has it on: normalize happens in-step, no fp32
        # round-trip of the batch (doc/tasks.md "Input fold")
        "input_fold": bool(getattr(tr, "input_fold", False)
                           and device_normalize),
    }
    if chain:
        detail["tail"] = ("partial chains dropped outside the timed "
                          "windows (a per-batch flush would compile the "
                          "non-chain step mid-window)")
    if chain_fallback:
        detail["chain_fallback"] = True
    return ips_raw / n_chips, detail


def h2d_bench(image, batch):
    """Isolated H2D bandwidth of the host-to-device path (uint8 and
    float32 batch payloads, pipelined single transfers) — one component
    of the e2e attribution."""
    import numpy as np
    import jax
    out = {}
    rng = np.random.RandomState(0)
    for name, arr in (
            ("u8", rng.randint(0, 255, (batch, image, image, 3),
                               np.uint8)),
            ("f32", rng.rand(batch, image, image, 3).astype(np.float32))):
        x = jax.device_put(arr)
        x.block_until_ready()
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            x = jax.device_put(arr)
            x.block_until_ready()
            ts.append(time.perf_counter() - t0)
        out[name] = {"mb_s": round(arr.nbytes / 1e6 / min(ts), 0),
                     "img_s_cap": round(batch / min(ts), 0)}
    return out


def decode_bench(image=224, n_img=256, threads=(1, 2, 4, 8)):
    """JPEG decode-pool scaling curve: in-memory-cached records through the
    real imgrec pipeline (decode + augment + batch, no training) at each
    ``decode_threads``. Proves the GIL-released native decode pool
    (io/native.py) actually parallelizes — the claim behind 'multi-core
    hosts scale the decode pool'. Reference analog: the OpenMP parallel
    decode loop (/root/reference/src/io/iter_image_recordio-inl.hpp:206-250).
    Returns {"threads": {t: img/s}, "host_cores": N}."""
    import os as _os
    from cxxnet_tpu.io.data import create_iterator

    cores = _os.cpu_count() or 1
    use = [t for t in threads if t <= 2 * cores] or [1]
    out = {}
    with tempfile.TemporaryDirectory() as td:
        rec = os.path.join(td, "decode.rec")
        _write_synthetic_recordio(rec, n_img, src_size=image + 32,
                                  classes=16)
        for t in use:
            cfg = [
                ("iter", "imgrec"),
                ("image_rec", rec),
                ("input_shape", f"3,{image},{image}"),
                ("batch_size", "64"),
                ("rand_crop", "1"),
                ("rand_mirror", "1"),
                ("decode_threads", str(t)),
                ("silent", "1"),
                ("iter", "end"),
            ]
            it = create_iterator(cfg)
            for b in it:          # warm epoch: page cache hot
                pass
            best = 0.0
            for _ in range(2):
                t0 = time.perf_counter()
                count = 0
                for b in it:
                    count += b.batch_size - b.num_batch_padd
                best = max(best, count / (time.perf_counter() - t0))
            out[t] = round(best, 2)
    return {"threads": out, "host_cores": cores}


class Budget:
    """Wall-clock budget (a run killed by the harness timeout leaves NO
    JSON line). Two mechanisms guarantee the line always lands:

    * cooperative — phases check ``remaining()`` and shrink/skip,
      recording what was dropped in ``truncated_phases`` (no silent
      caps);
    * watchdog — a daemon thread that, at expiry, prints the partial
      result accumulated so far and hard-exits. Whichever of the
      watchdog and the normal finish fires first wins the print (lock +
      done flag), so exactly one JSON line is ever emitted.

    The watchdog fires a MARGIN before the nominal budget: the harness
    runs this script under its own timeout, and a watchdog sleeping the
    full budget ties the race with an equal external kill. Firing ~3%
    early guarantees the line is on stdout while the process still owns
    it."""

    def __init__(self, seconds: float, partial: dict):
        self.t0 = time.time()
        self.seconds = seconds
        # ~3% early, floored at 2 s (serialization+print need real time)
        # but never more than 20% of a deliberately tiny smoke budget —
        # a 5 s budget must still run ~4 s of phases, not emit at t=0
        self.margin = min(20.0, max(2.0, 0.03 * seconds), 0.2 * seconds)
        self.partial = partial
        self.truncated: list = []
        self._lock = threading.Lock()
        self._done = False
        t = threading.Thread(target=self._watch, daemon=True,
                             name="bench-budget")
        t.start()

    def remaining(self) -> float:
        return self.seconds - (time.time() - self.t0)

    def low(self, need_s: float, phase: str) -> bool:
        """True (and records the skip) when under ``need_s`` of budget."""
        if self.remaining() < need_s:
            self.truncated.append(phase)
            return True
        return False

    def record(self, updates: dict) -> None:
        """Land partial results under the lock — the watchdog snapshots
        ``partial`` concurrently, and an unlocked dict mutation during
        its serialization would kill the emit this class guarantees."""
        with self._lock:
            self.partial.update(updates)

    def _watch(self) -> None:
        delay = self.seconds - self.margin - (time.time() - self.t0)
        if delay > 0:
            time.sleep(delay)
        with self._lock:
            if self._done:
                return
            self._done = True
            snap = dict(self.partial)
        snap["truncated_phases"] = self.truncated + [
            "budget exhausted mid-phase (watchdog emit)"]
        try:
            line = json.dumps(snap)
        except Exception:                # emit SOMETHING, never nothing
            line = json.dumps({
                "metric": "inception_bn_train_images_per_sec_per_chip",
                "value": None,
                "truncated_phases": ["watchdog serialization failed"]})
        finally:
            print(line, flush=True)
            os._exit(0)

    def finish(self, result: dict) -> None:
        with self._lock:
            if self._done:          # watchdog already printed
                return
            self._done = True
            if self.truncated:
                result["truncated_phases"] = self.truncated
            print(json.dumps(result), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--budget-s", type=float,
        default=float(os.environ.get("BENCH_BUDGET_S", "540")),
        help="wall-clock budget in seconds (env BENCH_BUDGET_S); phases "
             "shrink/skip to fit and the final JSON line always lands. "
             "Default 540 (not 600): the harness's own timeout is the "
             "600 s tier, and the emit must beat it with real margin, "
             "not tie it")
    ap.add_argument(
        "--full", action="store_true",
        help="run the float-e2e / h2d / decode-pool sub-benches too. "
             "The default run time-boxes to the phases that feed the "
             "metric of record: flagship compute, profile "
             "attribution, fp32 compare, ONE uint8 e2e window, and the "
             "secondary models (a run that sprawls over every phase "
             "dies to the harness timeout)")
    args = ap.parse_args()
    # ONE process, and it measures the chip: a child that queries
    # jax.devices() would hold the chip while this process waits for
    # it, and a CPU number must never land under a device metric's name
    import jax
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip: JAX found platform "
            f"{dev0.platform!r} ({dev0.device_kind}), not a TPU")
    from cxxnet_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    partial = {
        "metric": "inception_bn_train_images_per_sec_per_chip",
        "value": None, "unit": "images/sec/chip",
        "budget_s": args.budget_s,
    }
    budget = Budget(args.budget_s, partial)

    platform = dev0.platform
    # batch 256/chip is the BASELINE.md target configuration
    scale, image, classes, batch, steps = 1.0, 224, 1000, 256, 40
    e2e_steps = 24          # >=20-step window; slope over n1/n2

    # cooperative shrink: a tight budget trades window length (more
    # timing jitter) for completing at all; recorded, never silent
    rem = budget.remaining()
    if rem < 180:
        steps = max(3, steps // 4)
        e2e_steps = max(2, e2e_steps // 4)
        budget.truncated.append(f"steps shrunk 4x (budget {rem:.0f}s)")
    elif rem < 360:
        steps = max(3, steps // 2)
        e2e_steps = max(2, e2e_steps // 2)
        budget.truncated.append(f"steps shrunk 2x (budget {rem:.0f}s)")

    tr = make_trainer(scale, image, classes, batch, platform)
    n_dev = len(jax.devices())
    ref_fn = None
    if n_dev > 1 and batch % n_dev == 0:
        ref_fn = lambda: single_chip_cost(
            lambda bs: make_trainer(scale, image, classes, bs,
                                    f"{platform}:0-0"),
            batch // n_dev, classes)
    c = compute_bench(tr, image, classes, batch, steps, ref_cost_fn=ref_fn)
    budget.record({
        "value": round(c["ips"], 2),
        "vs_baseline": round(c["ips"] / BASELINE_IPS, 3),
        "mfu_pct": round(c["mfu_pct"], 2),
        "mfu_est": round(c["mfu_est"], 2),
        "achieved_flops": round(c["achieved_flops"], 1),
        "flops_source": c["flops_source"],
        "compute_dtype": c["compute_dtype"],
        "per_step_ms": round(c["per_step_ms"], 3),
        "arith_intensity": round(c["arith_intensity"], 1),
        "hbm_bytes_per_step": round(c["hbm_bytes_per_step"], 1),
        "loss_start": round(c["loss_start"], 4),
        "loss_end": round(c["loss_end"], 4),
        "n_chips": c["n_chips"],
        "chip": jax.devices()[0].device_kind,
    })
    # -- measured attribution: trace k steady steps and attribute device
    # op time per phase through the step's own scopes
    # (doc/ibn_perf.md; tools/ibn_perf.py regenerates the doc table).
    # No jax-0.9 dump carries memory counters, so the byte calibration
    # below stands on the analytic model alone
    if budget.low(75, "attribution"):
        att = {"skipped": "budget"}
    else:
        att = profile_attribution(tr, classes, batch, k=8)
    budget.record({"attribution": att})
    analytic = analytic_step_bytes(tr, batch)
    # trace bytes sum over ALL device planes (whole module) while
    # c["hbm_bytes_per_step"] is per-chip on multi-chip meshes — scale
    # the measured side to per-chip so the ratio compares like units
    meas = att.get("measured_bytes_per_step")
    if meas:
        meas = meas / max(1, c["n_chips"])
    # per-chip analytic share: activations split across the data axis,
    # the replicated param/optimizer passes run on every chip
    analytic_pc = (analytic["activation_bytes"] / max(1, c["n_chips"])
                   + analytic["param_bytes"])
    calib = calibration_entry(c["hbm_bytes_per_step"], meas, analytic_pc)
    budget.record({"calibration": calib})
    # -- input_fold (second kernel wave, this round): uint8 batches
    # normalize IN-STEP — cost-analysis bytes of the folded step vs the
    # f32-input step + the eager normalize it deletes
    if budget.low(60, "input_fold"):
        fold_entry = {"skipped": "budget"}
    elif c["n_chips"] > 1:
        # raw step_cost_analysis bytes are whole-module while the
        # headline bytes may be per-chip-normalized — the comparison
        # is only like-for-like on one chip (the standard bench rig)
        fold_entry = {"skipped": "multi-chip (byte units ambiguous; "
                                 "single-chip runs carry this)"}
    else:
        fold_entry = input_fold_entry(tr, c, image, classes, batch)
    budget.record({"input_fold": fold_entry})
    # bf16-vs-fp32 as a measured RATIO in the same JSON line: the
    # flagship conf computes in bf16 (gen_inception_bn emits
    # compute_dtype = bfloat16), so one fp32-policy rerun of the same
    # model prices the dtype lever directly. Short window (half steps) —
    # the ratio needs less precision than the headline number.
    # None only when the flagship already computes fp32 (no comparison
    # applies); a budget skip leaves an explicit marker so the ratio's
    # absence is distinguishable in the trajectory
    fp32_cmp = None
    if c["compute_dtype"] != "float32" and budget.low(120, "fp32_compare"):
        fp32_cmp = {"skipped": "budget"}
    elif c["compute_dtype"] != "float32":
        try:
            tr32 = make_trainer(scale, image, classes, batch, platform,
                                overrides=(("compute_dtype", "float32"),))
            c32 = compute_bench(tr32, image, classes, batch,
                                max(3, steps // 2))
            fp32_cmp = {
                "images_per_sec_per_chip": round(c32["ips"], 2),
                "per_step_ms": round(c32["per_step_ms"], 3),
                "achieved_flops": round(c32["achieved_flops"], 1),
                "mfu_est": round(c32["mfu_est"], 2),
                "hbm_bytes_per_step": round(c32["hbm_bytes_per_step"], 1),
                "compute_dtype": "float32",
                # >1 means the reduced-precision flagship step is faster
                "speedup_vs_f32": round(
                    c32["per_step_ms"] / c["per_step_ms"], 3)
                if c["per_step_ms"] else None,
            }
        except Exception as e:
            if _on_tpu():
                raise
            fp32_cmp = {"error": f"{type(e).__name__}: {e}"}
        else:
            # free the duplicate flagship (params, opt state, compiled
            # chain) before the HBM-heavy e2e/secondary phases
            del tr32, c32
    if fp32_cmp is not None:
        budget.record({"fp32_compare": fp32_cmp})
    e2e_chain = 4
    if budget.low(90, "e2e_u8"):
        e2e_u8, e2e_detail = None, {"skipped": "budget"}
    else:
        e2e_u8, e2e_detail = e2e_bench(tr, image, classes, batch,
                                       e2e_steps, device_normalize=1,
                                       chain=e2e_chain)
        budget.record({"e2e_u8_images_per_sec_per_chip": round(e2e_u8, 2)})
        if e2e_u8:
            # e2e phase MFU: achieved ips x per-image step FLOPs — shows
            # how much of the compute-path efficiency the data plane keeps
            fpi = c["step_tflop"] * 1e12 / batch
            ach = e2e_u8 * fpi
            e2e_detail["achieved_flops"] = round(ach, 1)
            e2e_detail["mfu_est"] = (
                round(100.0 * ach / 1e12 / c["peak_bf16_tflops"], 2)
                if c["peak_bf16_tflops"] else None)
    # float path: per-batch dispatch (a second chain compile would buy
    # nothing). --full only (with decode/h2d below): the default run is
    # time-boxed to ONE uint8 e2e window
    skip_marker = None if args.full else "--full only"
    if skip_marker or budget.low(60, "e2e_f32"):
        e2e_ips = None
    else:
        e2e_ips, _ = e2e_bench(tr, image, classes, batch,
                               max(4, e2e_steps // 3), chain=0)
        budget.record({"e2e_images_per_sec_per_chip": round(e2e_ips, 2)})
    if skip_marker or budget.low(45, "decode_pool"):
        dec = None
    else:
        dec = decode_bench(image=image, n_img=256)
    if skip_marker or budget.low(15, "h2d"):
        h2d = None
    else:
        h2d = h2d_bench(image, batch)
    # per-core decode rate -> host cores needed to keep one chip's compute
    # path fed (the e2e gap explanation, measured not asserted)
    dec_1t = dec["threads"].get(1, 0.0) if dec else 0.0
    if dec is not None:
        dec["cores_to_feed_compute"] = (round(c["ips"] / dec_1t, 1)
                                        if dec_1t else None)
    # attribution: a serial pipeline can do no better than its weakest
    # stage; all caps here are HOST-level (decode on this host's cores,
    # the shared H2D link, compute summed over the host's chips) and the
    # achieved rate is e2e_u8 x n_chips, so multi-chip runs compare like
    # with like. h2d is measured AFTER training; a ratio >100% means the
    # transfer/compute overlap beats the serial model.
    # None (not 0.0) for budget-skipped stages — same rule as the e2e
    # keys below: a zero reads as a measured throughput collapse
    stage_caps = {"decode_1t_ips": dec_1t or None,
                  "h2d_u8_ips_cap": (h2d["u8"]["img_s_cap"]
                                     if h2d else None),
                  "compute_ips_host": round(c["ips"] * c["n_chips"], 2)}
    nonzero = [v for v in stage_caps.values() if v]
    cap = min(nonzero) if nonzero else None
    e2e_detail.update(stage_caps)
    e2e_detail["h2d_state"] = "measured post-training"
    e2e_detail["achieved_vs_weakest_stage_pct"] = (
        round(100.0 * e2e_u8 * c["n_chips"] / cap, 1)
        if (cap and e2e_u8) else None)

    # -- secondary BASELINE.md models: same MFU/roofline treatment -------
    # AlexNet at the reference's own batch-256 memory recipe
    # (update_period=2 x batch 128, example/ImageNet/README.md:6-10) —
    # exercises 11x11 stride-4 + grouped conv + LRN + giant fullc;
    # kaggle_bowl exercises the small-image conv stack
    # (example/kaggle_bowl/bowl.conf). A secondary model failing its
    # loss-decrease self-check reports learning=false instead of voiding
    # the flagship number.
    def model_entry(name, conf, mbatch, msteps, mclasses, mimage,
                    baseline_ips, basis, overrides=()):
        try:
            mtr = make_conf_trainer(conf, mbatch, platform, overrides)
        except Exception as e:
            if _on_tpu():
                raise
            return {"error": f"{type(e).__name__}: {e}"}
        # same multi-chip whole-module-FLOPs guard as the flagship
        mref = None
        if n_dev > 1 and mbatch % n_dev == 0:
            mref = lambda: single_chip_cost(
                lambda bs: make_conf_trainer(conf, bs, f"{platform}:0-0",
                                             overrides),
                mbatch // n_dev, mclasses)
        try:
            mc = compute_bench(mtr, mimage, mclasses, mbatch, msteps,
                               ref_cost_fn=mref)
            learning = True
        except AssertionError:
            mc = None
            learning = False
        if mc is None:
            return {"learning": False}
        return {
            "images_per_sec_per_chip": round(mc["ips"], 2),
            "vs_baseline": (round(mc["ips"] / baseline_ips, 3)
                            if baseline_ips else None),
            "baseline_basis": basis,
            "mfu_pct": round(mc["mfu_pct"], 2),
            "mfu_est": round(mc["mfu_est"], 2),
            "achieved_flops": round(mc["achieved_flops"], 1),
            "flops_source": mc["flops_source"],
            "compute_dtype": mc["compute_dtype"],
            "roofline_pct": round(mc["roofline_pct"], 2),
            "arith_intensity": round(mc["arith_intensity"], 1),
            "hbm_bytes_per_step": round(mc["hbm_bytes_per_step"], 1),
            "step_tflop": round(mc["step_tflop"], 4),
            # device step time from the chained-dispatch slope — NOT wall
            # per-dispatch time, which bottoms out at the host's dispatch
            # cost and buries tiny models like bowl (~0.02 TFLOP/step)
            "per_step_ms": round(mc["per_step_ms"], 3),
            "flops_normalized": mc["flops_normalized"],
            "timing_method": mc["timing_method"],
            "loss_start": round(mc["loss_start"], 4),
            "loss_end": round(mc["loss_end"], 4),
            "learning": learning,
        }

    models = {}
    # batch 128 single-step (the update_period=2 batch-256 memory
    # recipe is exercised by the dryrun/tests; here it would double
    # the compile count for identical per-image cost)
    if not budget.low(150, "model:alexnet"):
        models["alexnet"] = model_entry(
            "alexnet", "examples/ImageNet/alexnet.conf", 128, 24,
            1000, 227, None,
            "no reference throughput published; the reference's "
            "memory note (example/ImageNet/README.md:6-10) is the "
            "only AlexNet baseline")
    if not budget.low(120, "model:kaggle_bowl"):
        models["kaggle_bowl"] = model_entry(
            "kaggle_bowl", "examples/kaggle_bowl/bowl.conf", 64, 40,
            121, 40, 10112.0,
            "implied from 'about 5 minute to train' on a GTX 780 "
            "(example/kaggle_bowl/README.md:26): 100 rounds x "
            "~30,336 NDSB images / 300 s ~= 10,112 img/s")

    budget.finish({
        "metric": "inception_bn_train_images_per_sec_per_chip",
        "value": round(c["ips"], 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(c["ips"] / BASELINE_IPS, 3),
        "model_tflops": round(c["model_tflops"], 2),
        "mfu_pct": round(c["mfu_pct"], 2),
        "mfu_est": round(c["mfu_est"], 2),
        "achieved_flops": round(c["achieved_flops"], 1),
        "flops_source": c["flops_source"],
        "compute_dtype": c["compute_dtype"],
        "roofline_pct": round(c["roofline_pct"], 2),
        "arith_intensity": round(c["arith_intensity"], 1),
        "hbm_bytes_per_step": round(c["hbm_bytes_per_step"], 1),
        "step_tflop": round(c["step_tflop"], 4),
        "per_step_ms": round(c["per_step_ms"], 3),
        "timing": ("k-step chained dispatch, slope of two chain lengths "
                   "(device time; cancels dispatch cost + one-off "
                   "recompiles)"
                   if c["timing_method"] == "chained"
                   else c["timing_method"]),
        "peak_bf16_tflops": c["peak_bf16_tflops"],
        "chip": jax.devices()[0].device_kind,
        "n_chips": c["n_chips"],
        # None (not 0.0) when the phase was budget-skipped — a zero here
        # reads as a measured throughput collapse downstream
        "e2e_images_per_sec_per_chip":
            None if e2e_ips is None else round(e2e_ips, 2),
        "e2e_u8_images_per_sec_per_chip":
            None if e2e_u8 is None else round(e2e_u8, 2),
        "e2e_attribution": e2e_detail,
        "h2d": h2d if h2d is not None
        else {"skipped": skip_marker or "budget"},
        "decode_pool": dec if dec is not None
        else {"skipped": skip_marker or "budget"},
        "loss_start": round(c["loss_start"], 4),
        "loss_end": round(c["loss_end"], 4),
        "fp32_compare": fp32_cmp,
        # measured per-phase attribution and the
        # measured-vs-cost_analysis byte calibration
        "attribution": att,
        "calibration": calib,
        "input_fold": fold_entry,
        "models": models,
        "bench_mode": "full" if args.full else "quick",
        "budget_s": args.budget_s,
    })


if __name__ == "__main__":
    main()
