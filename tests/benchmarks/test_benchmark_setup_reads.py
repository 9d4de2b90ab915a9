"""The set-up metrics (``benchmarks/setup_reads.py``): each reader pinned
on a ring filled by hand; what lies outside set-up left out; ``None``
where the ring holds no set-up span; and the CPU rehearsal over a copy
of the toy manifest that carries the five entries."""

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

from benchmarks import setup_reads  # noqa: E402
from cxxnet_tpu.telemetry.trace import TRACER  # noqa: E402

TOY = os.path.join(ROOT, "tests", "benchmarks", "data", "toy")
READERS = ("setup_trace_lower_s", "setup_compile_load_s",
           "setup_executables", "setup_model_s", "setup_input_s")
#: what each reader reads on :func:`filled`'s ring
PINNED = {"setup_trace_lower_s": 0.58, "setup_compile_load_s": 1.65,
          "setup_executables": 5, "setup_model_s": 1.1,
          "setup_input_s": 0.7}
T = 2000.0                  # the ring's spans, seconds on perf_counter
WINDOW = [("fetch", T + 6.1, T + 6.1001), ("update", T + 6.2, T + 6.3)]


def reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)        # each its own module, as run.py
    return mod.read


@pytest.fixture
def ring():
    TRACER.disable()
    TRACER.clear()
    TRACER.keep(("train", "setup"))
    yield TRACER
    TRACER.keep(())
    TRACER.clear()


def span(ring, name, a, b, tid=None, **args):
    ring.add_complete(name, T + a, T + b, cat="setup", args=args or None,
                      tid=tid)


def filled(ring):
    """A set-up as the harness walks it, seconds from ``T``."""
    # LearnTask: one executable
    span(ring, "setup.task", 0.0, 1.0)
    span(ring, "compile.trace", 0.2, 0.3, fn="a")
    span(ring, "compile.lower", 0.3, 0.35, fn="a")
    span(ring, "compile.backend", 0.35, 0.55, fn="a", cached=False)
    # the weights: a jitted initialiser whose trace traces an inner jit
    # and builds an eager op's executable
    span(ring, "setup.weights", 1.0, 2.0)
    span(ring, "compile.trace", 1.1, 1.2, fn="init")
    span(ring, "compile.trace", 1.12, 1.15, fn="inner")
    span(ring, "compile.backend", 1.16, 1.18, fn="eager", cached=False)
    span(ring, "compile.lower", 1.2, 1.25, fn="init")
    span(ring, "compile.backend", 1.25, 1.65, fn="init", cached=False)
    # the chain's construction, then (the harness's own work between
    # them, with an executable of its own) the first batch
    span(ring, "setup.input", 2.0, 2.5)
    span(ring, "compile.backend", 2.6, 2.9, fn="harness", cached=False)
    span(ring, "setup.input", 3.0, 3.2)
    # the warm-up round: the step, loaded; another thread's compile
    span(ring, "train.round", 3.2, 5.0, round=0)
    span(ring, "compile.trace", 3.3, 3.5, fn="step")
    span(ring, "compile.lower", 3.5, 3.6, fn="step")
    span(ring, "compile.backend", 3.6, 4.6, fn="step", cached=True)
    span(ring, "compile.backend", 3.7, 3.8, tid=999, fn="other",
         cached=False)
    # the reference check's executable, between the rounds
    span(ring, "compile.backend", 5.1, 5.5, fn="reference", cached=False)
    # the window's round: one compile before its first fetch, one in it
    span(ring, "train.round", 6.0, 10.0, round=1)
    span(ring, "compile.backend", 6.02, 6.05, fn="late", cached=False)
    span(ring, "compile.backend", 7.0, 7.5, fn="window", cached=False)
    ring.add_complete("train.step_dispatch", T + 6.2, T + 6.25, cat="train")
    return {"trace": None, "spans": WINDOW, "span_window_s": 3.9}


@pytest.mark.parametrize("name", READERS)
def test_reader_pinned_on_a_ring(ring, name):
    view = filled(ring)
    assert reader(name)(view) == pytest.approx(PINNED[name], abs=1e-6)


def test_the_parts_add_up_to_the_set_up_spans(ring):
    """Every instant of set-up goes to one innermost span."""
    seconds, _ = setup_reads.setup_seconds(filled(ring))
    union = 1.0 + 1.0 + 0.5 + 0.2 + 1.8 + 0.1      # train.round 1: to 6.1
    assert sum(seconds.values()) == pytest.approx(union, abs=1e-6)
    assert seconds["train.round"] == pytest.approx(1.8 - 1.3 + 0.1 - 0.03,
                                                   abs=1e-6)


def test_a_compile_outside_set_up_is_not_counted(ring):
    span(ring, "setup.input", 0.0, 1.0)
    span(ring, "compile.backend", 1.5, 2.0, fn="outside", cached=False)
    span(ring, "compile.trace", 2.0, 2.5, fn="outside")
    view = {"trace": None, "spans": WINDOW, "span_window_s": 3.9}
    assert reader("setup_executables")(view) == 0
    assert reader("setup_compile_load_s")(view) == 0.0
    assert reader("setup_trace_lower_s")(view) == 0.0
    assert reader("setup_input_s")(view) == pytest.approx(1.0)


@pytest.mark.parametrize("name", READERS)
def test_reader_has_nothing_to_read(ring, name):
    view = {"trace": None, "spans": WINDOW, "span_window_s": 3.9}
    assert reader(name)(view) is None                   # an empty ring
    # the loop's spans alone: a program that records no set-up
    ring.add_complete("train.step_dispatch", T + 6.2, T + 6.25, cat="train")
    ring.add_complete("train.data_wait", T + 6.1, T + 6.2, cat="train")
    assert reader(name)(view) is None
    # set-up spans, but no window to place them before
    filled(ring)
    assert reader(name)(dict(view, spans=[])) is None


def test_cpu_rehearsal_reports_the_set_up_metrics(tmp_path):
    """The toy manifest with the five entries added (written here: the
    toy manifest is the benchmark's, and is not edited). Their sum stays
    inside the harness's own marks of the same run."""
    work = tmp_path / "toy"
    shutil.copytree(TOY, work)
    with open(work / "BENCHMARK.json") as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest["per_layer"] += [m for m in json.load(f)["per_layer"]
                                  if m["name"] in READERS]
    (work / "BENCHMARK.json").write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", str(work / "BENCHMARK.json"), "--rehearse-cpu",
         "--workload", "toy_resident", "--seed", "2147483659",
         "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    out = lines[-1]
    assert out["correct"] is True
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(READERS) <= set(got)
    assert all(got[k] >= 0 for k in READERS)
    assert got["setup_executables"] >= 1
    marks = next(line["setup_split_s"] for line in lines
                 if "setup_split_s" in line)
    covered = sum(got[k] for k in READERS if k != "setup_executables")
    assert covered <= marks["learn_task_and_weights"] \
        + marks["feed_open"] + marks["warmup_round"]
