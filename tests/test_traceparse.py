"""telemetry.traceparse: the way back from a compiled step's text and a
profiler dump to the program's own names — ``scope_table`` and
``classify`` on hand-written text, the interval arithmetic on
hand-made events, the real step builders' scopes on a toy convnet
(compiled here, on the CPU backend), and the whole attribution pinned
on a two-step cut of a real dump of the flagship step on a v5e."""

import json
import os
import re

import numpy as np
import pytest

from cxxnet_tpu.telemetry import traceparse as tp
from cxxnet_tpu.telemetry import profiler

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmarks", "data")
#: two-step cuts of real dumps (tests/benchmarks/test_benchmark_program_
#: reads.py says what each is): (stem, scope table, pinned numbers)
CUTS = {
    "pr24_cut": ("ibn_resident_scoped_2steps", ".scope_table.json",
                 ".pinned.json"),
    "pr23_cut": ("ibn_resident_2steps", ".pr24_scopes.json",
                 ".pr24_pinned.json"),
}

HLO = """HloModule jit_one

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(one)/jvp(cv1)/mul"}
  ROOT %a = f32[8]{0} add(%m, %p), metadata={op_name="jit(one)/jvp(cv1)/add"}
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %n = f32[8]{0} negate(%q), metadata={op_name="jit(one)/optimizer/neg"}
  ROOT %b = f32[8]{0} bitcast(%n)
}

ENTRY %main.1 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %bn_act_bwd.3 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(one)/transpose(jvp(bn1))/fused.bn_act/bn_act_bwd/pallas_call"}
  %copy.4 = f32[8]{0} copy(%bn_act_bwd.3)
  ROOT %add.5 = f32[8]{0} add(%copy.4, %fusion.2), metadata={op_name="jit(one)/optimizer/fused.sgd_apply/add"}
}
"""


def test_scope_table_reads_op_names_and_fusion_roots():
    t = tp.scope_table(HLO)
    assert t["bn_act_bwd.3"].endswith("fused.bn_act/bn_act_bwd/pallas_call")
    # a fusion without metadata: its root's, else the first it holds
    assert t["fusion.1"] == "jit(one)/jvp(cv1)/add"
    assert t["fusion.2"] == "jit(one)/optimizer/neg"
    # what the compiler made of nothing the program traced stays out
    assert "copy.4" not in t
    assert t["x"] == "x" and tp.classify(t["x"]) == ("other", "", "")


@pytest.mark.parametrize("scope,want", [
    ("jit(one)/jvp(cv1)/conv_general_dilated", ("forward", "cv1", "")),
    ("jit(one)/transpose(jvp(cv1))/conv_general_dilated",
     ("backward", "cv1", "")),
    ("jit(one)/jvp(bn1)/fused.bn_act/bn_act_fwd/pallas_call",
     ("forward", "bn1", "bn_act")),
    ("jit(one)/transpose(jvp(bn1))/fused.bn_act/reshape",
     ("backward", "bn1", "bn_act")),
    ("jit(one)/optimizer/fused.sgd_apply/sgd_apply_update/pallas_call",
     ("optimizer", "optimizer", "sgd_apply")),
    ("jit(one)/optimizer/sub", ("optimizer", "optimizer", "")),
    ("jit(one)/input_fold/fused.stem/stem_fwd/pallas_call",
     ("forward", "input_fold", "stem")),
    # a jit holds a function's name, not a scope
    ("jit(one)/jvp(r1)/jit(relu)/max", ("forward", "r1", "")),
    ("jit(one)/jvp(jit(relu))/max", ("forward", "", "")),
    # control flow and call primitives are no layer
    ("jit(step)/while/body/jvp(cv1)/dot_general", ("forward", "cv1", "")),
    ("jit(one)/transpose(jvp(fc1))/fused.bias_act/shard_map/psum",
     ("backward", "fc1", "bias_act")),
    ("jit(one)/jvp(checkpoint(cv1))/rematted_computation/mul",
     ("forward", "cv1", "")),
    ("jit(one)/cond/branch_1_fun/optimizer/add",
     ("optimizer", "optimizer", "")),
    # outside every phase
    ("jit(one)/jit(_threefry_fold_in)/concatenate", ("other", "", "")),
    ("jit(one)/transpose(jvp())/convert_element_type",
     ("backward", "", "")),
    ("", ("other", "", "")),
    (None, ("other", "", "")),
])
def test_classify(scope, want):
    assert tp.classify(scope) == want


def test_scope_path_takes_wrappers_apart():
    transforms, scopes = tp.scope_path(
        "jit(one)/transpose(jvp(in3a.bn))/fused.bn_act/bn_act_bwd/"
        "pallas_call")
    assert transforms == {"jit", "transpose", "jvp"}
    assert scopes == ["in3a.bn", "fused.bn_act", "bn_act_bwd"]


def test_own_times_do_not_count_a_body_twice():
    events = sorted([("while", 0, 100), ("a", 10, 20), ("cond", 40, 50),
                     ("b", 45, 30), ("c", 120, 10)],
                    key=lambda e: (e[1], -e[2]))
    own = {n: t for n, _, _, t in tp._own_times(events)}
    assert own == {"while": 30, "a": 20, "cond": 20, "b": 30, "c": 10}


def test_gaps_are_what_no_interval_covers():
    assert tp._gaps([(2, 3), (5, 7), (9, 14)], 0, 10) \
        == [(0, 2), (3, 5), (7, 9)]
    assert tp._gaps([], 0, 4) == [(0, 4)]
    assert tp._gaps([(0, 4)], 0, 4) == []


class _Tracer:
    """to_ts_us of a tracer whose epoch is perf_counter 100.0 s."""
    @staticmethod
    def to_ts_us(perf_s):
        return (perf_s - 100.0) * 1e6


def test_place_spans_and_gap_owner_on_the_dumps_clock():
    # the profiler started at unix 5_000_000_000 ns = perf 102.0 s; the
    # dump counts from unix 4_999_000_000 ns (1 ms earlier)
    clock = (5_000_000_000, 102.0)
    spans = [{"name": "train.step_dispatch", "ph": "X",
              "ts": 2.5e6, "dur": 1000.0},                 # perf 102.5
             {"name": "train.h2d_stage", "ph": "X",
              "ts": 2.5e6 + 100.0, "dur": 50.0},           # nested
             {"name": "marker", "ph": "i", "ts": 0.0}]
    host = tp.place_spans(spans, _Tracer, clock, 4_999_000_000)
    assert [h[0] for h in host] == ["train.step_dispatch",
                                    "train.h2d_stage"]
    # perf 102.5 s is 0.5 s after the clock moment, which is 1 ms in
    assert host[0][1] == pytest.approx(501_000_000)
    assert host[0][2] - host[0][1] == pytest.approx(1_000_000)
    # a gap inside both: the innermost wins; one inside neither
    assert tp.gap_owner((501_110_000, 501_140_000), host) \
        == "train.h2d_stage"
    assert tp.gap_owner((501_500_000, 501_600_000), host) \
        == "train.step_dispatch"
    assert tp.gap_owner((0, 10), host) == "elsewhere"


def test_no_dump_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        tp.attribute_profile(str(tmp_path))


def test_cpu_dump_has_no_chip_to_read(tmp_path):
    """The whole bracket on the CPU backend — device tracer only, the
    public options — leaves a dump, and the attribution says None
    rather than guess from host events."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with profiler.device_trace(str(tmp_path)) as (unix_ns, perf_s):
        f(x).block_until_ready()
    assert unix_ns > 1_600_000_000 * 10 ** 9 and perf_s > 0
    assert tp.find_xplane(str(tmp_path)).endswith(".xplane.pb")
    assert tp.attribute_profile(str(tmp_path), hlo_text=HLO) is None
    assert tp.attribution_fragment(None) == ""


# -- the real step builders, compiled here ------------------------------------

TOY = """
netconfig = start
layer[0->1] = conv:cv1
  kernel_size = 3
  nchannel = 8
  pad = 1
layer[1->2] = batch_norm:bn1
layer[2->3] = relu:r1
layer[3->4] = max_pooling:p1
  kernel_size = 2
  stride = 2
layer[4->5] = flatten:fl
layer[5->6] = fullc:fc1
  nhidden = 4
layer[6->6] = softmax:sm
netconfig = end
input_shape = 3,16,16
batch_size = 16
dev = cpu
eta = 0.1
momentum = 0.9
"""


def _toy_step_table(extra=""):
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer(parse_config_string(TOY + extra))
    tr.init_model()
    batch = DataBatch(data=np.zeros((16, 16, 16, 3), np.float32),
                      label=np.zeros((16, 1), np.float32))
    tr.update(batch)        # registers the step it ran, lowers nothing
    return tr, tp.scope_table(profiler.step_hlo_text())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_train_step_names_every_op_it_traces(dtype):
    tr, table = _toy_step_table(f"compute_dtype = {dtype}\n")
    assert profiler.step_scope_table() == table     # memoised, the same
    seen = {}
    for name, scope in table.items():
        if not scope.startswith("jit("):
            # an argument's copy carries the argument's name
            continue
        phase, layer, kind = tp.classify(scope)
        if phase == "other":
            # only the rng key chain is in no phase
            assert "threefry" in scope, (name, scope)
        seen.setdefault(phase, {}).setdefault(layer, set()).add(kind)
    assert {"forward", "backward", "optimizer"} <= set(seen)
    # every layer that holds parameters is there forward and backward
    for layer in ("cv1", "bn1", "fc1"):
        assert layer in seen["forward"] and layer in seen["backward"]
    assert set(seen["optimizer"]) == {"optimizer"}
    # no op of the step sits under a ``fused.<kind>`` scope, and the
    # selection log agrees: a convnet's ops choose no implementation
    assert not any(k for by in seen.values() for ks in by.values()
                   for k in ks)
    assert tr.net.fused_log == {}


@pytest.mark.parametrize("period,do_update", [(1, True), (2, False),
                                              (2, True)])
def test_step_builders_share_one_optimizer_scope(period, do_update):
    """``_apply_grads`` is what the std, sp and pp builders all call:
    accumulation and update trace under ``optimizer`` there, once."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.trainer import Trainer, _apply_grads
    tr = Trainer(parse_config_string(TOY))
    tr.init_model()
    zeros = jax.tree_util.tree_map(jnp.zeros_like, tr.params)

    def fn(params, opt_state, accum, grads, sched):
        return _apply_grads(tr.optimizer, period, do_update, params,
                            opt_state, accum, grads, sched)
    text = jax.jit(fn).lower(
        tr.params, tr.opt_state, zeros if period > 1 else {}, zeros,
        tr._sched_scalars()).compile().as_text()
    scopes = [s for s in tp.scope_table(text).values() if "/" in s]
    assert scopes
    for s in scopes:
        assert tp.classify(s)[0] == "optimizer", s
        assert s.count("optimizer") == 1, s


def test_layer_scope_names_are_sanitised():
    import jax
    from cxxnet_tpu.model import layer_scope

    def f(x):
        with layer_scope("in 3a/1x1:conv[0]"):
            return x * 2.0
    text = jax.jit(f).lower(1.0).compile().as_text()
    assert any("in_3a_1x1_conv_0_" in s
               for s in tp.scope_table(text).values())


# -- the whole attribution, on a cut of a real dump -----------------------------


@pytest.fixture(params=sorted(CUTS))
def cut(request):
    """``(path of the cut, its scope table, its pinned numbers)``."""
    stem, table_ext, pinned_ext = CUTS[request.param]
    base = os.path.join(DATA, stem)
    if not os.path.exists(base + table_ext):
        pytest.skip(f"no {request.param}: it is cut from a chip's dump of "
                    "this tree (tests/benchmarks/make_trace_fixture.py)")
    with open(base + table_ext) as f:
        table = json.load(f)
    with open(base + pinned_ext) as f:
        return base + ".xplane.pb.gz", table, json.load(f)


def _as_hlo_text(table):
    """A compiled module's text carrying just these op_names."""
    return "ENTRY %main (x: f32[]) -> f32[] {\n" + "\n".join(
        f'  %{name} = f32[] add(%x, %x), metadata={{op_name="{scope}"}}'
        for name, scope in table.items()) + "\n}\n"


def test_attribute_profile_pinned_on_a_real_dump(cut):
    path, table, pinned = cut
    att = tp.attribute_profile(path, hlo_text=_as_hlo_text(table))
    assert att["steps"] == 2 and att["device"] == "/device:TPU:0"
    assert att["step_ms"] == pytest.approx(pinned["step_ms"], rel=1e-6)
    for phase, ms in pinned["phases_ms"].items():
        assert att["phases"][phase]["ms"] == pytest.approx(ms, rel=1e-6)
    # each instruction counted once, by its own time: the phases add up
    # to the busy time
    assert sum(d["ms"] for d in att["phases"].values()) \
        == pytest.approx(att["busy_ms"], rel=1e-6)
    assert att["kinds"]["forward"]["bn_act"] == pytest.approx(
        pinned["kinds_ms"]["forward/bn_act"], rel=1e-6)
    assert att["kinds"]["backward"]["bn_act"] == pytest.approx(
        pinned["kinds_ms"]["backward/bn_act"], rel=1e-6)
    assert att["unattributed_pct"] == pytest.approx(
        pinned["unattributed_pct"], rel=1e-6)
    assert att["layers"][0][0] == pinned["top_layer"]
    assert att["idle_gaps"] == []           # no clock given: not named
    frag = tp.attribution_fragment(att)
    assert frag.startswith("profile[step:") and "backward:" in frag
    assert "kinds[" in frag and "forward/bn_act:" in frag
    assert "layers[" in frag and "gaps[" not in frag


def test_a_cut_has_no_start_time_to_place_spans_by(cut):
    """The cuts hold no Task Environment plane: without the dump's
    start time no span is placed, and no gap is named by guesswork."""
    path, table, pinned = cut
    att = tp.attribute_profile(
        path, hlo_text=_as_hlo_text(table), clock=(0, 0.0),
        spans=[{"name": "train.metric_drain", "ph": "X",
                "ts": 0.0, "dur": 1e9}])
    assert att["idle_gaps"] == []
    assert att["idle_pct"] == pytest.approx(pinned["idle_pct"], rel=1e-6)


def test_without_a_scope_table_everything_is_unattributed(cut):
    att = tp.attribute_profile(cut[0], hlo_text="")
    assert att["unattributed_pct"] == pytest.approx(100.0)
    assert att["phases"]["forward"]["ms"] == 0.0
    assert att["layers"] == []
    assert re.match(r"^[\w.\-]+$", att["top_unattributed"][0][0])
