"""bench.py helpers: analytic byte model, calibration entry, profile
attribution plumbing, and the input_fold pricing — the sanity layer
under the BENCH artifact's new calibrated fields."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench  # noqa: E402


@pytest.fixture(scope="module")
def tiny_trainer():
    # image 64 = the bench's own CPU smoke scale (32 under-runs the
    # inception pool pyramid)
    tr = bench.make_trainer(0.25, 64, 8, 8, "cpu:0-0")
    return tr


def test_calibration_entry_measured():
    e = bench.calibration_entry(100.0, 80.0, 120.0)
    assert e["measured_vs_cost_ratio"] == pytest.approx(0.8)
    assert e["analytic_vs_cost_ratio"] == pytest.approx(1.2)
    assert e["hbm_bytes_per_step_calibrated"] == 80.0
    assert e["source"] == "trace"


def test_calibration_entry_unmeasured():
    e = bench.calibration_entry(100.0, None, 120.0)
    assert e["measured_vs_cost_ratio"] is None
    assert e["measured_bytes_per_step"] is None
    # no measurement -> the calibrated field falls back to the model,
    # and says so
    assert e["hbm_bytes_per_step_calibrated"] == 100.0
    assert "cost_analysis" in e["source"]


def test_calibration_entry_zero_guard():
    e = bench.calibration_entry(0.0, 0.0, 0.0)
    assert e["measured_vs_cost_ratio"] is None
    assert e["analytic_vs_cost_ratio"] is None


def test_analytic_bytes_scales_with_batch(tiny_trainer):
    b8 = bench.analytic_step_bytes(tiny_trainer, 8)
    b16 = bench.analytic_step_bytes(tiny_trainer, 16)
    assert b8["total"] > 0
    # activation traffic scales with batch; param traffic does not
    assert b16["activation_bytes"] == pytest.approx(
        2 * b8["activation_bytes"])
    assert b16["param_bytes"] == b8["param_bytes"]
    assert b8["total"] == pytest.approx(
        b8["activation_bytes"] + b8["param_bytes"])


def test_profile_attribution_and_calibration(tiny_trainer):
    """End-to-end: bracket a few real flagship steps, read the dump, and
    build the calibration entry — the exact path bench.main runs. The
    attribution is a chip's: on the CPU backend the dump has no device
    plane, and the entry says so instead of reading host events; the
    byte ratio is then the analytic cross-check, not a fabricated
    measurement."""
    att = bench.profile_attribution(tiny_trainer, 8, 8, k=2)
    assert "no chip's plane" in att["error"], att
    cost = tiny_trainer.step_cost_analysis(_batch(tiny_trainer, 8, 8))
    analytic = bench.analytic_step_bytes(tiny_trainer, 8)
    e = bench.calibration_entry(cost["bytes_accessed"],
                                att.get("measured_bytes_per_step"),
                                analytic["total"])
    assert e["cost_analysis_bytes_per_step"] > 0
    assert e["analytic_vs_cost_ratio"] > 0
    assert e["measured_vs_cost_ratio"] is None
    # the step the bracket ran described itself on the way
    from cxxnet_tpu.telemetry import profiler, traceparse
    phases = {traceparse.classify(s)[0]
              for s in profiler.step_scope_table().values()}
    assert {"forward", "backward", "optimizer"} <= phases


def _batch(tr, batch, classes):
    from cxxnet_tpu.io.data import DataBatch
    rng = np.random.RandomState(0)
    c, y, x = tr.graph.input_shape
    b = DataBatch(
        data=rng.rand(batch, y, x, c).astype(np.float32),
        label=rng.randint(0, classes, size=(batch, 1)).astype(
            np.float32))
    return b


def test_input_fold_entry(tiny_trainer):
    c = {"hbm_bytes_per_step": float(
        tiny_trainer.step_cost_analysis(
            _batch(tiny_trainer, 8, 8))["bytes_accessed"])}
    e = bench.input_fold_entry(tiny_trainer, c, 64, 8, 8)
    assert "error" not in e, e
    assert e["active"] is True
    # the folded step must not pay the f32-input step's input bytes
    # AND the eager normalize traffic on top
    assert e["step_bytes_folded"] < (e["step_bytes_f32_input"]
                                     + e["eager_normalize_extra_bytes"])
    assert e["bytes_saved_per_step"] > 0


def test_full_flag_exists():
    """--full is the time-box contract (ROADMAP 5b): default runs skip
    the float-e2e/h2d/decode sub-benches."""
    src = open(os.path.join(os.path.dirname(bench.__file__),
                            "bench.py")).read()
    assert "--full" in src
    assert '"skipped": skip_marker or "budget"' in src


def test_dp_mesh_bench_record_parses():
    """ROADMAP 5(a) follow-through: a budgeted compute_bench on the dp
    mesh (the 8-CPU-device test default) lands a record that JSON
    round-trips (the driver's ``parsed != null``) and says how many
    chips it ran on; it carries no kernel-selection tag, there being
    nothing to select."""
    import json

    tr = bench.make_trainer(0.25, 64, 8, 8, "cpu")
    assert tr.mesh.num_devices > 1          # genuinely a dp mesh
    c = bench.compute_bench(tr, 64, 8, 8, 2)
    parsed = json.loads(json.dumps(
        {k: c[k] for k in ("ips", "per_step_ms", "hbm_bytes_per_step",
                           "n_chips")}))
    assert parsed is not None
    assert parsed["n_chips"] > 1
    assert "fused_kernels" not in c and "fused_on_mesh" not in c

