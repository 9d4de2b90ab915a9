"""``step_mfu_pct`` (PR 25): the whole step's share of the chip's bf16
peak. The reader on two-step cuts of real dumps of the flagship step on
a v5e; the count the harness hands it, read out of ``run.py`` itself in
the CPU rehearsal, the same with ``fused_kernels`` 0 and 1; the reader's
arithmetic on fabricated views: the mean over the cell's devices of
``steps / window_s``, never clamped, nothing where there is no trace."""

import gzip
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops, trace_reduce  # noqa: E402

DATA = os.path.join(ROOT, "tests", "benchmarks", "data")
TOY = os.path.join(DATA, "toy")
NAME = "step_mfu_pct"
PEAKS = flops.chip_peaks("TPU v5 lite")
#: the cuts of ``ibn_resident`` (256 rows, one v5e): PR 23's, and the one
#: cut on this PR's chip, whose ``.pinned.json`` carries this metric
CUTS = {"pr23_cut": "ibn_resident_2steps",
        "pr24_cut": "ibn_resident_scoped_2steps"}
#: what ``run.py`` counted for a step of ``ibn_resident`` on the chip:
#: the traced runs' value x window / steps x peak gives it back to the
#: last digit (my chip runs, PR 25); 11.960 GFLOP an image
FLAGSHIP_STEP_FLOPS = 3061650554880.0
#: and for a step of the toy convnet's 8 rows, in the CPU rehearsal
TOY_STEP_FLOPS = 2661888.0


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return load(os.path.join(ROOT, "benchmarks", "layer_metrics",
                             name + ".py"), "bench_" + name).read


read = reader(NAME)


def fabricated(steps=10, window_s=1.0, chips=1):
    """A view whose devices each made ``steps`` steps in ``window_s``;
    at the defaults 197 TFLOP a second a chip: 100 % of a v5e."""
    dev = {"steps": steps, "window_s": window_s, "busy_s": window_s}
    return {"trace": {"devices": [dict(dev) for _ in range(chips)],
                      "host": []},
            "step_flops": 19.7e12 * chips, "chips": chips, "peaks": PEAKS}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_reader_on_a_cut_of_a_real_dump(cut):
    from jax.profiler import ProfileData
    base = os.path.join(DATA, CUTS[cut])
    with open(base + ".mxu_calls.json") as f:
        mxu = frozenset(json.load(f))
    with gzip.open(base + ".xplane.pb.gz", "rb") as f:
        trace = trace_reduce.read(
            ProfileData.from_serialized_xspace(f.read()), mxu)
    devices = [trace_reduce.reduce_device(d)
               for d in trace["devices"].values()]
    view = {"trace": {"devices": devices, "host": []},
            "step_flops": FLAGSHIP_STEP_FLOPS, "rows": 256, "chips": 1,
            "peaks": PEAKS}
    got = read(view)
    with open(base + ".pinned.json") as f:
        pinned = json.load(f)
    if NAME in pinned:
        assert got == pytest.approx(pinned[NAME], rel=1e-9)
    # the value times the cut's step period gives back the count over
    # the peak: nothing but whole step periods is in it
    (dev,) = devices
    assert dev["steps"] == 2
    assert got / 100.0 * dev["window_s"] / dev["steps"] == pytest.approx(
        FLAGSHIP_STEP_FLOPS / (PEAKS["bf16_tflops"] * 1e12), rel=1e-12)
    # the flagship's step with the fused suite is a tenth of the chip,
    # and under the convolutions' own share: they take a part of the
    # step's time for all of its counted work
    assert 9.5 < got < 11.0
    assert got < reader("conv_mxu_pct")(view) < 100.0


@pytest.mark.parametrize("fused", ["0", "1"])
def test_the_harness_counts_the_configuration(tmp_path, fused):
    """The CPU rehearsal of ``run.py`` on a copy of the toy manifest with
    this PR's entry and a probe that hands back ``view["step_flops"]``:
    the harness's own count is the same with the Pallas suite off and on
    (interpreted here), and the metric itself is left out, for want of a
    trace."""
    work = tmp_path / "toy"
    shutil.copytree(TOY, work)
    with open(work / "BENCHMARK.json") as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest["per_layer"] += [m for m in json.load(f)["per_layer"]
                                  if m["name"] == NAME]
    manifest["per_layer"].append(dict(
        manifest["per_layer"][-1], name="step_flops_seen", unit="flop"))
    (work / "BENCHMARK.json").write_text(json.dumps(manifest))
    (work / "layer_metrics").mkdir()
    (work / "layer_metrics" / "step_flops_seen.py").write_text(
        "def read(view):\n    return view['step_flops']\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", CXXNET_FUSED_KERNELS=fused)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", str(work / "BENCHMARK.json"), "--rehearse-cpu",
         "--workload", "toy_resident", "--seed", "2147483693",
         "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    out = lines[-1]
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    (said,) = [line["fused_kernels"] for line in lines
               if "fused_kernels" in line]
    assert bool(said.get("fused")) == (fused == "1")  # two implementations
    assert out["metrics"]["step_flops_seen"]["value"] == TOY_STEP_FLOPS
    assert NAME not in out["metrics"]
    assert "compiles_in_window" in out["metrics"]   # its siblings read


@pytest.mark.parametrize("chips", [1, 4])
def test_reader_is_the_mean_over_the_cells_devices(chips):
    # the global count over the chips, against each device's steps
    view = fabricated(chips=chips)
    assert read(view) == pytest.approx(100.0)
    assert read(fabricated(134, 20.3, chips)) == pytest.approx(
        100.0 * (134 / 20.3) / 10.0, rel=1e-12)
    # a chip that falls behind lowers it, wherever it sits in the mesh
    view["trace"]["devices"][-1]["window_s"] = 2.0
    assert read(view) == pytest.approx(100.0 * (chips - 0.5) / chips)
    # not the busy time: idle gaps are inside whole periods and lower
    # the value only through them
    view["trace"]["devices"][0]["busy_s"] = 0.01
    assert read(view) == pytest.approx(100.0 * (chips - 0.5) / chips)


def test_an_impossible_reading_is_returned_as_it_is():
    """A stale count, or a window that is not whole steps, has to show
    as a share above 100, not hide under a ``min``."""
    assert read(fabricated(steps=25)) == pytest.approx(250.0)


def test_reader_has_nothing_to_read():
    assert read(dict(fabricated(), trace=None)) is None     # the CPU run
    # a trace of no whole step: left out, never a share of 0
    assert read(fabricated(window_s=0.0)) is None
    assert read(fabricated(steps=0)) is None


def test_the_manifest_carries_the_entry_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        found = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    # once, and with no ``workloads`` key: reported wherever the rate
    # is, in the cells later PRs add too
    assert len(found) == 1 and "workloads" not in found[0]
    assert found[0]["moves"] == "train_items_per_s_chip"
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "layer_metrics", NAME + ".py"))
