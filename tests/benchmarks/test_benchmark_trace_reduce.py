"""benchmarks/trace_reduce.py: the arithmetic on a hand-made interval
set with known answers, and the whole reduction pinned on a cut-down
copy of one real trace of the flagship step on a v5e."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce as tr  # noqa: E402
from benchmarks.trace_reduce import Ev  # noqa: E402

DATA = os.path.join(ROOT, "tests", "benchmarks", "data")


def test_merge_total_clip_subtract():
    assert tr.merge([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)]) \
        == [(0, 4), (5, 12)]
    assert tr.total([(0, 4), (5, 12)]) == 11
    assert tr.clip([(0, 4), (5, 12)], 3, 6) == [(3, 4), (5, 6)]
    # a 0..10 with holes punched at 2..3, 5..7 and past the end
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 14)]) \
        == [(0, 2), (3, 5), (7, 9)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_self_times_do_not_count_a_body_twice():
    events = sorted([
        Ev("%while.1", "while", 0, 100),             # spans its body
        Ev("%fusion.1", "fusion:kLoop", 10, 20),
        Ev("%conditional.1", "conditional", 40, 50),     # in the while
        Ev("%fusion.2", "convolution fusion", 45, 30),   # in both
        Ev("%fusion.3", "fusion:kLoop", 120, 10),        # alone
    ], key=lambda e: (e.start, -e.dur))
    own = {e.name: t for e, t in tr.self_times(events)}
    assert own == {"%while.1": 30, "%fusion.1": 20, "%conditional.1": 20,
                   "%fusion.2": 30, "%fusion.3": 10}
    assert sum(own.values()) == 110      # the union of the intervals


def test_category_reads_the_instruction_text():
    conv = ("%fusion.92 = bf16[256,14,14,160]{0,3,2,1:T(8,128)(2,1)S(1)} "
            "fusion(bf16[256,14,14,128]{3,2,1,0} %reshape.1366, f32[3,3,128,"
            "160]{2,3,1,0} %copy-done.312), kind=kOutput, "
            "calls=%fused_computation.135")
    assert tr.category(conv) == ("%fusion.92", "fusion:kOutput")
    assert tr.category(conv, {"%fused_computation.135"}) \
        == ("%fusion.92", "convolution fusion")
    kernel = ("%jvp__.109 = (bf16[50176,160]{1,0:T(8,128)(2,1)}, f32[1,160]"
              "{1,0:T(1,128)}) custom-call(bf16[50176,160]{1,0} %reshape.1),"
              ' custom_call_target="tpu_custom_call", operand_layout_'
              "constraints={bf16[50176,160]{1,0}}")
    name, cat = tr.category(kernel)
    assert (name, cat) == ("%jvp__.109", tr.PALLAS)
    assert tr.is_pallas(Ev(name, cat, 0, 1))
    assert tr.category("%copy.992 = bf16[256,14,14,128]{3,2,1,0} "
                       "copy(bf16[256,14,14,128]{3,0,2,1} %fusion.344)") \
        == ("%copy.992", "copy")
    start = ("%all-reduce-start.3 = f32[1024]{0} all-reduce-start(f32[1024]"
             "{0} %fusion.7), channel_id=3, replica_groups={{0,1,2,3}}")
    assert tr.is_collective(Ev(*tr.category(start), 0, 1))
    assert tr.category("garbage") == ("garbage", "unknown")
    hlo = """HloModule jit_one

%fused_computation.135 (param_0: bf16[8]) -> bf16[8] {
  %p = bf16[8] parameter(0)
  ROOT %c = bf16[8] convolution(bf16[8] %p, bf16[8] %p), window={size=1}
}

%fused_computation.7 (param_0: f32[4]) -> f32[4] {
  ROOT %a = f32[4] add(f32[4] %param_0, f32[4] %param_0)
}

ENTRY %main.1 (a: f32[4,4]) -> f32[4,4] {
  ROOT %d = f32[4,4] dot(f32[4,4] %a, f32[4,4] %a)
}
"""
    assert tr.mxu_computations(hlo) == {"%fused_computation.135",
                                        "%main.1"}


def hand_made_device():
    """Four runs of the step, at 0, 1000, 2000 and 3000 ns: the first is
    left out, so the window is 1000..3000, two whole periods. Each run:
    a convolution fusion 0..400; a Pallas kernel 400..600 that holds a
    10 ns bitcast; an all-reduce in flight 500..800 on the async line,
    half hidden behind the kernel and a later fusion 700..750, with its
    ``-done`` wait 750..800 on the ops line; idle 800..1000 but for one
    small module in the first gap of the window."""
    ops, asyncs, modules = [], [], []
    for k in range(4):
        t = 1000 * k
        modules.append(Ev("jit_one(1)", "", t, 800))
        ops += [
            Ev("%fusion.conv", "convolution fusion", t, 400),
            Ev("%bn_act_fwd", tr.PALLAS, t + 400, 200),
            Ev("%custom-call.9", "custom-call:ConcatBitcast", t + 450, 10),
            Ev("%fusion.add", "fusion:kLoop", t + 700, 50),
            Ev("%all-reduce-done.1", "all-reduce-done", t + 750, 50),
        ]
        asyncs.append(Ev("%all-reduce-start.1", "all-reduce-start",
                         t + 500, 300))
        asyncs.append(Ev("%copy-start.4", "copy-start", t + 100, 700))
    modules.append(Ev("jit_norm(2)", "", 1850, 20))
    ops.append(Ev("%fusion.norm", "fusion:kLoop", 1850, 20))
    order = lambda e: (e.start, -e.dur)
    return {"ops": sorted(ops, key=order), "async": sorted(asyncs, key=order),
            "modules": sorted(modules, key=order)}


def test_reduction_of_the_hand_made_set():
    dev = hand_made_device()
    assert tr.step_module(dev["modules"]) == "jit_one(1)"
    assert tr.window_of(dev) == (1000, 3000, 2)
    assert tr.window_of({"modules": dev["modules"][:2]}) is None
    red = tr.reduce_device(dev)
    ns = 1e-9
    assert red["steps"] == 2
    assert red["window_s"] == pytest.approx(2000 * ns)
    # busy: 0..600 and 700..800 of each period, and the 20 ns module
    assert red["busy_s"] == pytest.approx((700 + 700 + 20) * ns)
    assert red["pallas_s"] == pytest.approx(2 * 190 * ns)   # less the bitcast
    assert red["mxu_s"] == pytest.approx(2 * 400 * ns)
    assert red["relayout_s"] == 0
    assert red["collective_s"] == pytest.approx(2 * 300 * ns)
    # of each all-reduce's 500..800 the kernel hides 500..600 and the
    # fusion 700..750: exposed 600..700 and the wait 750..800
    assert red["collective_exposed_s"] == pytest.approx(2 * 150 * ns)
    assert red["gaps"] == [(1600, 1700), (1800, 1850), (1870, 2000),
                           (2600, 2700), (2800, 3000)]
    assert red["by_cat"]["convolution fusion"] == (pytest.approx(800 * ns), 2)
    assert red["by_name"]["convolution fusion %fusion.conv"] \
        == pytest.approx(800 * ns)


def test_idle_gaps_are_named_for_what_the_host_was_doing():
    red = tr.reduce_device(hand_made_device())
    host = [Ev("fetch", "", 1790, 70),       # covers 1800..1850 wholly
            Ev("update", "", 1860, 60),      # 50 of 1870..2000
            Ev("probe_sync", "", 1920, 200),     # 80 of 1870..2000
            Ev("update", "", 2850, 100)]     # 100 of 2800..3000
    out = tr.breakdown(red, host, n_ops=2, n_gaps=3)
    assert out["device_ops"] == [
        ["convolution fusion x1/step", pytest.approx(800e-9)],
        [tr.PALLAS + " x1/step", pytest.approx(380e-9)]]
    assert out["idle_gaps"] == [
        ["update", pytest.approx(200e-9)],
        ["probe_sync", pytest.approx(130e-9)],
        ["elsewhere", pytest.approx(100e-9)]]
    assert tr.gap_owner((1800, 1850), host) == "fetch"
    # a span nested in another covers as much and says more
    host.append(Ev("metric_drain", "", 2900, 40))
    assert tr.gap_owner((2910, 2930), host) == "metric_drain"
    assert tr.gap_owner((2800, 3000), host) == "update"


def test_host_spans_are_put_on_the_trace_clock():
    """``run.Profiler.place``: a span's perf_counter seconds become
    nanoseconds from the start of the profile, through the Unix time
    taken beside ``t_start`` and the dump's ``profile_start_time``."""
    from benchmarks.run import Profiler
    p = Profiler("unused")
    p.t_start, p.unix_ns_at_start = 100.0, 5_000_000_000
    assert p.place([("update", 100.5, 100.75), ("fetch", 100.25, 100.5)],
                   4_000_000_000) == [
        Ev("fetch", "", 1_250_000_000, 250_000_000),
        Ev("update", "", 1_500_000_000, 250_000_000)]
    assert p.place([("update", 100.5, 100.75)], None) == []


def test_a_dump_says_when_its_clock_started(tmp_path):
    """The real profiler, on the CPU backend, as a traced run starts it
    (no host tracer): ``read`` finds ``profile_start_time``, and it is
    the moment ``start_trace`` was called, on the Unix clock."""
    import time
    import jax.numpy as jnp
    from benchmarks.run import Profiler
    p = Profiler(str(tmp_path / "dump"))
    before = time.time_ns()
    p.start()
    jnp.ones((8, 8)).sum().block_until_ready()
    p.stop()
    trace = tr.read(tr.find_xplane(p.dir))
    assert trace["devices"] == {}            # no chip here
    assert before - 1_000_000 <= trace["start_unix_ns"] \
        <= p.unix_ns_at_start


def test_layer_metric_readers_on_the_hand_made_set():
    """Every reader of benchmarks/layer_metrics, fed the hand-made view:
    the per-step and per-cent arithmetic, and nothing returned where
    there is nothing to read."""
    import importlib.util
    import json

    def reader(name):
        path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                            name + ".py")
        spec = importlib.util.spec_from_file_location("lm_" + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
    red = tr.reduce_device(hand_made_device())
    view = {
        # the second update holds a 1 ms train-metric drain; the drain
        # at the round's end is in no update and is not taken off
        "spans": [("fetch", 0.0, 0.1), ("update", 0.1, 0.102),
                  ("fetch", 0.5, 0.6), ("update", 0.6, 0.605),
                  ("metric_drain", 0.603, 0.604),
                  ("metric_drain", 0.7, 0.9)],
        "span_window_s": 2.0, "compiles_in_window": 0,
        "trace": {"devices": [red, red], "host": []},
        # 2 chips: 1.6e3 operations a step each; 800 ns of MXU ops over
        # two steps -> 4e9 op/s of a 100 TFLOP/s peak = 0.004 %
        "step_flops": 3.2e3, "rows": 8, "chips": 2,
        "peaks": {"bf16_tflops": 100.0},
    }
    assert reader("compiles_in_window")(view) == 0
    assert reader("data_wait_pct")(view) == pytest.approx(10.0)
    assert reader("dispatch_ms_per_step")(view) == pytest.approx(3.0)
    assert reader("pallas_ms_per_step")(view) == pytest.approx(190e-6)
    assert reader("relayout_ms_per_step")(view) == 0
    assert reader("collective_exposed_ms")(view) == pytest.approx(150e-6)
    assert reader("device_idle_pct")(view) == pytest.approx(
        100 * (1 - 1420 / 2000))
    assert reader("conv_mxu_pct")(view) == pytest.approx(0.004)
    blind = dict(view, trace=None, spans=[])
    for name in ("pallas_ms_per_step", "conv_mxu_pct", "data_wait_pct",
                 "relayout_ms_per_step",
                 "collective_exposed_ms", "device_idle_pct",
                 "dispatch_ms_per_step"):
        assert reader(name)(blind) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        for m in json.load(f)["per_layer"]:
            assert callable(reader(m["name"]))


def test_reduction_pinned_on_the_cut_down_real_trace():
    """Two steps of the flagship (Inception-BN, 256 rows, bf16) on one
    v5e, cut out of a traced ``ibn_resident`` run of PR 23 with
    ``tests/benchmarks/make_trace_fixture.py``: read through
    ``ProfileData`` as a run reads its own dump, and reduced to the
    numbers the metrics use."""
    import gzip
    import json
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(
            DATA, "ibn_resident_2steps.xplane.pb.gz")) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, "ibn_resident_2steps.mxu_calls.json")) as f:
        mxu_calls = frozenset(json.load(f))
    trace = tr.read(profile, mxu_calls)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    dev = trace["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 4 and len(dev["async"]) == 0
    assert tr.step_module(dev["modules"]).startswith("jit_one(")
    assert trace["start_unix_ns"] is None     # the cut keeps no stat
    red = tr.reduce_device(dev)
    assert red["steps"] == 2
    pinned = json.load(open(os.path.join(
        DATA, "ibn_resident_2steps.pinned.json")))
    for key, want in pinned["seconds"].items():
        assert red[key] == pytest.approx(want, rel=1e-9), key
    # what the step is made of: 144 Pallas kernels (72 fused sites,
    # forward and backward) and one convolution fusion per conv layer
    # and pass
    assert red["by_cat"][tr.PALLAS][1] == 2 * 144
    assert red["by_cat"]["convolution fusion"][1] \
        == 2 * pinned["conv_fusions_per_step"]
    assert "unknown" not in red["by_cat"]
    # the device never waits for the host in this cell
    assert 1 - red["busy_s"] / red["window_s"] < 0.001
    out = tr.breakdown(red, [])
    assert out["device_ops"][0][0] == tr.PALLAS + " x144/step"
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 5
