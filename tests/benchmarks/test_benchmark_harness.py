"""benchmarks/run.py walked end to end on the CPU backend, at a toy size.

Each case is one run of the command in a process of its own, as the
driver makes them; the last line of its standard output is the result.
No number here is a device number: the last line names ``cpu``.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
TOY = os.path.join(ROOT, "tests", "benchmarks", "data", "toy")
#: the contract's five and ``compared``, which comes last in the line
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
#: what only a chip's trace can give: absent from every CPU line
DEVICE_DERIVED = {"pallas_ms_per_step", "conv_mxu_pct",
                  "collective_exposed_ms", "device_idle_pct"}


def run(*args, manifest=None, rehearse=True, timeout=300, **more_env):
    cmd = [sys.executable, RUN]
    if manifest is not None:
        cmd += ["--manifest", manifest]
    if rehearse:
        cmd += ["--rehearse-cpu"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **more_env)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(cmd + list(args), cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,chips,trace", [
    ("toy_resident", 1, 0),
    ("toy_records", 1, 1),
    ("toy_alexnet_resident", 1, 0),
    ("toy_dp4", 4, 1),
    ("toy_lm_resident", 1, 0),
    ("toy_lm_resident", 1, 1),
])
def test_cpu_rehearsal_ends_in_the_contracts_last_line(cell, chips, trace):
    out = last_line(run(
        "--workload", cell, "--seed", "3000000019", "--seconds", "1",
        "--trace", str(trace),
        manifest=os.path.join(TOY, "BENCHMARK.json")))
    assert set(out) == RESULT_KEYS and list(out)[-1] == "compared"
    assert "tolerance" in out["compared"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    names = set(out["metrics"])
    if trace:
        assert names == {"compiles_in_window", "data_wait_pct",
                         "dispatch_ms_per_step"}
        assert not names & DEVICE_DERIVED
        assert out["metrics"]["compiles_in_window"]["value"] == 0
    else:
        assert names == {"train_items_per_s_chip", "setup_s"}
        assert out["metrics"]["train_items_per_s_chip"]["value"] > 0
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}


def test_a_manifest_cell_off_the_chip_exits_nonzero_with_no_result():
    proc = run("--workload", "ibn_resident", "--seed", "1", "--seconds",
               "1", "--trace", "0", rehearse=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not 'tpu'" in proc.stderr
    # and the rehearsal switch is refused for the root manifest
    proc = run("--workload", "ibn_resident", "--seed", "1", "--seconds",
               "1", "--trace", "0")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_a_set_up_that_compiled_hands_the_window_to_a_fresh_process(tmp_path):
    """On a cold persistent compile cache the first child builds the
    executables and stops before the window; a second child loads them
    and makes the run, with the first's time in ``setup_s``. On a warm
    cache there is one child. One result line either way."""
    def walk():
        proc = run("--workload", "toy_resident", "--seed", "3000000021",
                   "--seconds", "1", "--trace", "0",
                   manifest=os.path.join(TOY, "BENCHMARK.json"),
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"))
        said = [json.loads(line) for line in proc.stdout.splitlines()
                if line.startswith("{")]
        children = [(s["attempt"], s["built_in_setup"] > 0)
                    for s in said if "built_in_setup" in s]
        results = [s for s in said if "correct" in s]
        assert len(results) == 1 and results[0] == last_line(proc)
        assert results[0]["correct"] is True
        split = next(s["setup_split_s"] for s in said if "setup_split_s" in s)
        assert results[0]["metrics"]["setup_s"]["value"] \
            == pytest.approx(sum(split.values()), abs=0.5)
        return children, split["before_this_process"]
    children, before = walk()
    assert children == [(1, True), (2, False)] and before > 2.0
    children, before = walk()
    assert children == [(1, False)] and before < 2.0


NEW_FEED = '''
"""A feed a later PR brings: the resident batch, handed out twice per
fetch so that it differs from every feed the benchmark has."""
import importlib.util, os

def _resident(ctx):
    path = os.path.join(ctx["root"], "benchmarks", "feeds", "resident.py")
    spec = importlib.util.spec_from_file_location("resident_feed", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

def section(traffic, ctx):
    return _resident(ctx).section(traffic, ctx)

def open(task, tr, traffic, ctx):
    ctx["say"](feed="brand_new", note=traffic["note"])
    return _resident(ctx).open(task, tr, traffic, ctx)
'''

NEW_METRIC = '''
"""A per-layer metric a later PR brings: steps the window's spans saw."""

def read(view):
    return sum(1 for name, _, _ in view["spans"] if name == "update")
'''


def test_a_cell_made_only_of_new_files_runs(tmp_path):
    """The add-files-only property: a new configuration, traffic mix,
    feed and per-layer metric, in a directory of their own with their
    own manifest entries, run without an edit to benchmarks/."""
    before = {
        os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(os.path.join(ROOT, "benchmarks"))
        if ".cache" not in d and "__pycache__" not in d for f in fs}
    for sub in ("configs", "traffic", "feeds", "layer_metrics"):
        os.makedirs(tmp_path / sub)
    shutil.copy(os.path.join(TOY, "configs", "toy_convnet.conf"),
                tmp_path / "configs" / "newnet.conf")
    (tmp_path / "configs" / "newnet.json").write_text(json.dumps({
        "name": "newnet", "source": "a test", "net": {"conf": "newnet.conf"},
        "overrides": ["eta = 0.02"], "input_shape": [3, 16, 16],
        "num_class": 10, "compute_dtype": "float32", "item": "image",
        "check": "train_loss", "reduced": [], "assumed": []}))
    (tmp_path / "traffic" / "new_mix.json").write_text(json.dumps({
        "name": "new_mix", "feed": "brand_new", "rows_per_chip": 4,
        "note": "from a data file"}))
    (tmp_path / "feeds" / "brand_new.py").write_text(NEW_FEED)
    (tmp_path / "layer_metrics" / "steps_seen.py").write_text(NEW_METRIC)
    with open(os.path.join(TOY, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "newnet", "source": "a test",
                            "file": "configs/newnet.json", "reduced": [],
                            "why": "add-files-only"}]
    manifest["workloads"] = [{"name": "new_cell", "config": "newnet",
                              "traffic": "new_mix", "chips": 1,
                              "why": "add-files-only"}]
    manifest["per_layer"].append({
        "name": "steps_seen", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "trainer",
        "moves": "train_items_per_s_chip", "workloads": ["new_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    proc = run("--workload", "new_cell", "--seed", "7", "--seconds", "1",
               "--trace", "1", manifest=str(tmp_path / "BENCHMARK.json"))
    out = last_line(proc)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["metrics"]["steps_seen"]["value"] > 0
    assert "dispatch_ms_per_step" in out["metrics"]   # an old reader too
    assert '"feed": "brand_new", "note": "from a data file"' in proc.stdout
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before
