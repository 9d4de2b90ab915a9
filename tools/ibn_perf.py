#!/usr/bin/env python
"""Measure and write doc/ibn_perf.md — the Inception-BN per-phase
attribution table (the flagship analog of doc/alexnet_perf.md).

What it measures, on whatever backend is attached:

* a jax.profiler trace of k steady-state chained train steps, parsed
  into the conv / BN+act / pool / LRN / matmul / optimizer / h2d phase
  table (cxxnet_tpu.telemetry.traceparse — ROADMAP item 1's "attribute
  the ~72% non-MXU step time");
* the byte calibration: measured trace bytes (TPU memory counters)
  vs XLA cost_analysis vs the doc/bytes_audit.md-style analytic model.

Run on a TPU-attached host for the numbers of record:
    python tools/ibn_perf.py
A CPU run produces a real (measured) table too — clearly labeled, at
the CPU smoke scale — so the doc never goes stale silently.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "examples", "ImageNet"))

import bench  # noqa: E402  (repo-root bench.py — reuses its helpers)
from cxxnet_tpu.telemetry.traceparse import PHASE_ORDER  # noqa: E402


def measure():
    import jax
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)
    if on_accel:
        scale, image, classes, batch, steps, k = 1.0, 224, 1000, 256, 24, 8
    else:
        scale, image, classes, batch, steps, k = 0.25, 64, 16, 8, 3, 3
    tr = bench.make_trainer(scale, image, classes, batch, platform)
    c = bench.compute_bench(tr, image, classes, batch, steps)
    att = bench.profile_attribution(tr, classes, batch, k=k)
    analytic = bench.analytic_step_bytes(tr, batch)
    calib = bench.calibration_entry(
        c["hbm_bytes_per_step"],
        att.get("measured_bytes_per_step"), analytic["total"])
    fold = bench.input_fold_entry(tr, c, image, classes, batch)
    chip = jax.devices()[0].device_kind
    return {"chip": chip, "platform": platform, "scale": scale,
            "image": image, "batch": batch, "c": c, "att": att,
            "calib": calib, "fold": fold}


def render(m) -> str:
    c, att, calib = m["c"], m["att"], m["calib"]
    lines = [
        "# Inception-BN step: measured per-phase attribution",
        "",
        "Generated by `python tools/ibn_perf.py` (rerun on a "
        "TPU-attached host to refresh it; `bench.py` carries the same "
        "attribution under `attribution`/`calibration`). Method: one jax.profiler trace "
        "of steady-state train steps, the chip's op events attributed "
        "to forward / backward / optimizer through the step's own "
        "`jax.named_scope`s by `cxxnet_tpu/telemetry/traceparse.py`; the "
        "spirit of the reference's doc/alexnet_perf.md, applied to the "
        "flagship.",
        "",
        f"Backend: **{m['platform']} / {m['chip']}**, flagship scale "
        f"{m['scale']}, image {m['image']}, batch {m['batch']}, "
        f"compute dtype {c['compute_dtype']}, per-step "
        f"{c['per_step_ms']:.2f} ms (chained-dispatch slope).",
        "",
    ]
    if m["platform"] == "cpu":
        lines += [
            "> **CPU-scale capture.** No accelerator was attached when "
            "this revision was generated. The attribution reads a "
            "chip's trace (`/device:TPU:<n>`) and says so where there "
            "is none; the byte figures below are of the scaled-down "
            "flagship on the CPU backend, magnitudes NOT transferable "
            "to TPU. Regenerate on a TPU host for the numbers of "
            "record.",
            "",
        ]
    if "error" in att:
        lines += [f"Attribution failed: `{att['error']}`", ""]
    else:
        lines += [
            "## Per-phase attribution (share of summed device-op time)",
            "",
            "| phase | ms/step | % of op time | events/step | fused "
            "kinds inside it (ms/step) |",
            "|---|---|---|---|---|",
        ]
        phases = att.get("phases", {})
        for ph in PHASE_ORDER:
            d = phases.get(ph)
            if d:
                kinds = ", ".join(
                    f"{k} {v:.2f}" for k, v in
                    att.get("kinds", {}).get(ph, {}).items() if k != "xla")
                lines.append(f"| {ph} | {d['ms']:.3f} | {d['pct']:.1f} "
                             f"| {d['count']} | {kinds} |")
        lines += [
            "",
            f"Total op time {att['busy_ms']:.2f} ms/step over "
            f"{att['steps']} traced steps of {att['step_ms']:.2f} ms, "
            f"device idle {att['idle_pct']:.2f} %. Heaviest layers: "
            + ", ".join(f"`{n}` ({ms:.2f} ms)"
                        for n, ms in att.get("layers", [])[:4])
            + "; heaviest instructions no scope reaches: "
            + ", ".join(f"`{n}` ({ms:.2f} ms)"
                        for n, ms in att.get("top_unattributed", [])[:4])
            + ".",
            "",
        ]
    lines += [
        "## Byte calibration (measured vs cost_analysis vs analytic)",
        "",
        "| source | bytes/step |",
        "|---|---|",
        f"| XLA cost_analysis (pre-fusion estimate) | "
        f"{calib['cost_analysis_bytes_per_step']:.3e} |",
        f"| trace memory counters (measured) | "
        + (f"{calib['measured_bytes_per_step']:.3e} |"
           if calib["measured_bytes_per_step"] else "not recorded |"),
        f"| analytic model (doc/bytes_audit.md style) | "
        f"{calib['analytic_bytes_per_step']:.3e} |",
        "",
    ]
    if calib["measured_vs_cost_ratio"]:
        lines.append(
            f"**Calibration ratio (measured / cost_analysis): "
            f"{calib['measured_vs_cost_ratio']:.3f}** — every "
            "`roofline_pct` bench.py reports divides by this to "
            "become a chip number; <1 means XLA fused below its own "
            "pre-fusion estimate, so a >100% roofline reading is a "
            "real fusion win, not measurement error.")
    else:
        lines.append(
            "This backend's trace carries no per-op memory counters, so "
            "`hbm_bytes_per_step` stays cost_analysis-based "
            f"(analytic/cost cross-check ratio "
            f"{calib['analytic_vs_cost_ratio']}); the measured column "
            "fills in on a TPU capture.")
    lines.append("")
    fold = m["fold"]
    if "error" not in fold:
        lines += [
            "## Input fold + stem padding",
            "",
            f"`input_fold` (uint8 batch normalized in-step, no fp32 "
            f"round-trip of the input): folded step "
            f"{fold['step_bytes_folded']:.3e} B vs f32-input step "
            f"{fold['step_bytes_f32_input']:.3e} B plus "
            f"{fold['eager_normalize_extra_bytes']:.3e} B of eager "
            f"normalize traffic it deletes — "
            f"**{fold['bytes_saved_per_step']:.3e} B/step saved** "
            "(cost-analysis accounting; the measured carrier is "
            "`e2e_u8_images_per_sec_per_chip`). Stem channel padding "
            "(`stem_pad`, RGB->4) is a graph-level plan "
            "(`graph.stem_pad_plan`).",
            "",
        ]
    return "\n".join(lines)


def main() -> int:
    m = measure()
    print(json.dumps({k: v for k, v in m.items()
                      if k in ("chip", "platform")}, indent=None))
    out = os.path.join(REPO, "doc", "ibn_perf.md")
    with open(out, "w") as f:
        f.write(render(m) + "\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
