"""The layer metrics that read the program's own names (PR 24): each
reader on two-step cuts of real dumps of the flagship step on a v5e,
joined to the step's scope table (``CUTS``); the span readers on a ring
filled by hand; every reader's ``None`` where there
is nothing to read; and the CPU rehearsal over a manifest that carries
the new entries."""

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

from benchmarks import program_reads, trace_reduce  # noqa: E402
from cxxnet_tpu.telemetry import profiler  # noqa: E402
from cxxnet_tpu.telemetry.trace import TRACER  # noqa: E402

DATA = os.path.join(ROOT, "tests", "benchmarks", "data")
#: two-step cuts of real dumps of the flagship step on a v5e, each with
#: the scope table it is joined to and the numbers pinned on it:
#: * ``pr24_cut`` - cut from a traced run of PR 24's tree, with the table
#:   of the step that chip compiled (every kernel event carries its kind);
#: * ``pr23_cut`` - PR 23's checked-in cut (the parent's tree: its 144
#:   Pallas events still read ``jvp__.N`` and stay unattributed) joined to
#:   the table of PR 24's step compiled for a described v5e, whose other
#:   instruction names coincide (98 % of event time has the same name,
#:   shape and opcode, the rest differs in an opcode's spelling).
CUTS = {
    "pr24_cut": ("ibn_resident_scoped_2steps", ".mxu_calls.json",
                 ".scope_table.json", ".pinned.json"),
    "pr23_cut": ("ibn_resident_2steps", ".mxu_calls.json",
                 ".pr24_scopes.json", ".pr24_pinned.json"),
}
TOY = os.path.join(DATA, "toy")
DEVICE_READERS = ("forward_ms_per_step", "backward_ms_per_step",
                  "optimizer_ms_per_step", "fused_site_ms_per_step",
                  "fused_bn_ms_per_step", "scope_unattributed_pct")
SPAN_READERS = ("enqueue_ms_per_step", "h2d_stage_ms_per_step")


def reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)        # each its own module, as run.py
    return mod.read


def new_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    return [m for m in per_layer
            if m["name"] in DEVICE_READERS + SPAN_READERS]


@pytest.fixture(params=sorted(CUTS))
def scoped(request, monkeypatch):
    """A cut's view, and the program describing the step by the cut's
    scope table (no step is lowered here)."""
    import gzip
    from jax.profiler import ProfileData
    stem, mxu_ext, table_ext, pinned_ext = CUTS[request.param]
    base = os.path.join(DATA, stem)
    if not os.path.exists(base + table_ext):
        pytest.skip(f"no {request.param}: it is cut from a chip's dump of "
                    "this tree (tests/benchmarks/make_trace_fixture.py)")
    with open(base + mxu_ext) as f:
        mxu = frozenset(json.load(f))
    with gzip.open(base + ".xplane.pb.gz", "rb") as f:
        trace = trace_reduce.read(
            ProfileData.from_serialized_xspace(f.read()), mxu)
    devices = [trace_reduce.reduce_device(d)
               for d in trace["devices"].values()]
    with open(base + table_ext) as f:
        table = json.load(f)
    monkeypatch.setitem(profiler._step, "scopes", table)
    with open(base + pinned_ext) as f:
        pinned = json.load(f)
    view = {"trace": {"devices": devices, "host": []}, "spans": [],
            "span_window_s": 0.0, "cut": request.param}
    return view, pinned


def test_the_manifest_carries_the_eight_entries_appended():
    """PR 24's eight and the eight they were appended to are there, each
    once; where in ``per_layer`` is nobody's business: a later PR puts
    its own wherever it likes."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    for name in (
            "forward_ms_per_step", "backward_ms_per_step",
            "optimizer_ms_per_step", "fused_site_ms_per_step",
            "fused_bn_ms_per_step", "scope_unattributed_pct",
            "enqueue_ms_per_step", "h2d_stage_ms_per_step",
            "compiles_in_window", "data_wait_pct", "dispatch_ms_per_step",
            "pallas_ms_per_step", "relayout_ms_per_step", "conv_mxu_pct",
            "collective_exposed_ms", "device_idle_pct"):
        assert names.count(name) == 1, name


@pytest.mark.parametrize("name", DEVICE_READERS)
def test_device_reader_pinned_on_the_scoped_fixture(scoped, name):
    view, pinned = scoped
    assert reader(name)(view) == pytest.approx(pinned[name], rel=1e-6)


def test_phases_and_the_rest_add_up_to_the_busy_time(scoped):
    view, pinned = scoped
    dev = view["trace"]["devices"][0]
    busy_ms = 1e3 * dev["busy_s"] / dev["steps"]
    phases = sum(reader(n)(view) for n in DEVICE_READERS[:3])
    rest = reader("scope_unattributed_pct")(view) / 100.0 * busy_ms
    assert phases + rest == pytest.approx(busy_ms, rel=1e-6)
    pallas = 1e3 * dev["pallas_s"] / dev["steps"]
    relayout = 1e3 * dev["relayout_s"] / dev["steps"]
    site = reader("fused_site_ms_per_step")(view)
    assert reader("fused_bn_ms_per_step")(view) <= site
    kernels = [k.rsplit(" ", 1)[1] for k in dev["by_name"]
               if k.startswith(trace_reduce.PALLAS)]
    assert kernels
    if view["cut"] == "pr23_cut":
        # the parent's kernels are anonymous: the sites hold only the
        # re-layouts attributed to them, and the kernels are the rest
        assert any("jvp__" in k for k in kernels)
        assert 0 < site <= relayout
        assert rest == pytest.approx(pallas, rel=0.1)
        return
    # the fused sites: no less than their kernels, no more than the
    # kernels and every re-layout of the step
    assert pallas <= site <= pallas + relayout
    # no Pallas event is anonymous any more: each carries its kind
    assert not [k for k in kernels if "jvp__" in k]
    assert {k.lstrip("%").rsplit(".", 1)[0] for k in kernels} \
        >= {"bn_act_fwd", "bn_act_bwd", "sgd_apply_update"}


@pytest.mark.parametrize("name", DEVICE_READERS)
def test_device_reader_has_nothing_to_read(scoped, monkeypatch, name):
    view, _ = scoped
    # the CPU rehearsal: no device trace
    assert reader(name)(dict(view, trace=None)) is None
    # a program that describes no step (no trainer ran one): no table
    monkeypatch.setitem(profiler._step, "scopes", {})
    assert reader(name)(view) is None
    # a program from before PR 24: no step_scope_table to import
    monkeypatch.delattr(profiler, "step_scope_table")
    assert reader(name)(view) is None


@pytest.fixture
def ring():
    TRACER.disable()
    TRACER.clear()
    TRACER.keep(("train",))
    yield TRACER
    TRACER.keep(())
    TRACER.clear()


def test_span_readers_clip_to_the_unprofiled_window(ring):
    """Three steps inside the window, one before it and one after (the
    profiled tail): 2 ms in step_dispatch and 0.2 + 0.1 ms in the two
    h2d_stage calls of each step."""
    t = 1000.0
    for i in range(-1, 4):
        s = t + 0.1 * i
        ring.add_complete("train.data_wait", s, s + 0.001, cat="train")
        ring.add_complete("train.h2d_stage", s, s + 0.0002, cat="train")
        ring.add_complete("train.h2d_stage", s + 0.002, s + 0.0021,
                          cat="train")
        ring.add_complete("train.step_dispatch", s + 0.002, s + 0.004,
                          cat="train", args={"step": i})
        ring.add_complete("train.metric_drain", s + 0.004, s + 0.09,
                          cat="train")
    # the benchmark's own spans: the first starts the window
    view = {"trace": None, "span_window_s": 0.295,
            "spans": [("fetch", t + 0.05, t + 0.051),
                      ("fetch", t, t + 0.001), ("update", t + 0.002, t + 0.1)]}
    assert reader("enqueue_ms_per_step")(view) == pytest.approx(2.0)
    assert reader("h2d_stage_ms_per_step")(view) == pytest.approx(0.3)
    assert program_reads.span_ms_per_step(view, "train.metric_drain") \
        == pytest.approx(86.0)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_has_nothing_to_read(ring, name):
    view = {"trace": None, "span_window_s": 1.0,
            "spans": [("fetch", 5.0, 5.1)]}
    assert reader(name)(view) is None               # an empty ring
    ring.add_complete("train.data_wait", 5.0, 5.1, cat="train")
    assert reader(name)(view) is None               # no step in it
    assert reader(name)(dict(view, spans=[])) is None
    assert reader(name)(dict(view, span_window_s=0.0)) is None


def test_cpu_rehearsal_reports_the_span_metrics_and_no_device_one(tmp_path):
    """The toy manifest with this PR's entries added (written here: the
    toy manifest is the benchmark's, and is not edited)."""
    work = tmp_path / "toy"
    shutil.copytree(TOY, work)
    with open(work / "BENCHMARK.json") as f:
        manifest = json.load(f)
    for m in new_entries():
        m = dict(m)
        m.pop("workloads", None)
        manifest["per_layer"].append(m)
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
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    got = out["metrics"]
    assert not set(got) & set(DEVICE_READERS)
    assert set(SPAN_READERS) <= set(got)
    # the program's span and the benchmark's span from outside agree
    assert got["enqueue_ms_per_step"]["value"] == pytest.approx(
        got["dispatch_ms_per_step"]["value"], rel=0.5, abs=0.3)
    assert 0 <= got["h2d_stage_ms_per_step"]["value"] \
        < got["enqueue_ms_per_step"]["value"]
