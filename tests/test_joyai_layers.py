"""The layer kinds JoyAI-LLM-Flash needs (PR 28) — ``rmsnorm``, ``ffn``
with ``act = swiglu``, ``mla``, ``moe`` with ``router = sigmoid`` —
against the configuration's own plain reference
(``benchmarks/references/joyai_llm_flash.py``, which imports nothing of
the program) on seeded weights, at a small size on the CPU: hidden 64, 4
heads, ranks 48 / 32, head parts 16 / 8 / 16, 16 experts of width 32
with top-3."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.graph import LayerSpec
from cxxnet_tpu.layers import ApplyCtx, create_layer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
E, S, B = 64, 32, 2


def load_reference(name="joyai_llm_flash_for_layers"):
    spec = importlib.util.spec_from_file_location(name, os.path.join(
        ROOT, "benchmarks", "references", "joyai_llm_flash.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()

#: the keys of the configuration file the reference reads, at the small
#: size (tests/benchmarks/data/joyai_toy has the same)
C = {"hidden_size": E, "num_attention_heads": 4, "q_lora_rank": 48,
     "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
     "v_head_dim": 16, "rope_theta": 32000000.0, "rms_norm_eps": 1e-6,
     "n_routed_experts_published": 16, "n_routed_experts": 16,
     "expert_first": 0, "num_experts_per_tok": 3, "norm_topk_prob": True,
     "routed_scaling_factor": 2.5, "n_shared_experts": 1,
     "moe_intermediate_size": 32, "bias_update_rate": 0.001}

MLA_CFG = [("nhead", "4"), ("q_lora_rank", "48"), ("kv_lora_rank", "32"),
           ("qk_nope_head_dim", "16"), ("qk_rope_head_dim", "8"),
           ("v_head_dim", "16"), ("rope_theta", "32000000"),
           ("init_sigma", "0.2")]


def make(kind, cfg):
    return create_layer(LayerSpec(kind, "L", [0], [1], list(cfg)), [])


def moe_layer(first=0, held=16, **extra):
    cfg = [("router", "sigmoid"), ("num_expert", "16"), ("topk", "3"),
           ("nhidden", "32"), ("shared_expert", "1"),
           ("routed_scaling_factor", "2.5"), ("expert_first", str(first)),
           ("expert_held", str(held)), ("init_sigma", "0.3")]
    return make("moe", cfg + [(k, str(v)) for k, v in extra.items()])


def ctx(train=True):
    return ApplyCtx(train=train, compute_dtype=jnp.float32)


def x_node(seed=0, rows=B):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, S, 1, E))


def seq(x):
    return x.reshape(x.shape[0], x.shape[1], x.shape[3])


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), \
        np.max(np.abs(a - b))


def tree_close(a, b, tol=2e-5):
    jax.tree_util.tree_map(lambda u, v: close(u, v, tol), a, b)


def test_rmsnorm_is_the_references():
    layer = make("rmsnorm", [])
    p = {"gamma": 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (E,))}
    x = x_node()
    (y,), _ = layer.apply(p, {}, [x], ctx())
    with jax.default_matmul_precision("highest"):
        close(seq(y), ref.rms(seq(x), p["gamma"], 1e-6))
    assert layer.init_params(jax.random.PRNGKey(0), [(E, S, 1)]).keys() \
        == {"gamma"}
    from cxxnet_tpu.optim import tag_for_param
    assert tag_for_param("gamma") == "bias"


def test_swiglu_ffn_is_the_references():
    layer = make("ffn", [("act", "swiglu"), ("nhidden", "96"),
                         ("init_sigma", "0.2")])
    p = layer.init_params(jax.random.PRNGKey(2), [(E, S, 1)])
    assert set(p) == {"g", "h", "o"} and "bias" not in p["g"]
    x = x_node()

    def prog(p, x):
        return seq(layer.apply(p, {}, [x], ctx())[0][0])
    with jax.default_matmul_precision("highest"):
        close(prog(p, x), ref.swiglu(seq(x), p))
        g1 = jax.grad(lambda p, x: jnp.sum(prog(p, x) ** 2), (0, 1))(p, x)
        g2 = jax.grad(lambda p, x: jnp.sum(ref.swiglu(seq(x), p) ** 2),
                      (0, 1))(p, x)
    tree_close(g1, g2)


@pytest.mark.parametrize("impl", ["auto", "ref", "flash"])
def test_mla_forward_and_gradients_are_the_references(impl):
    layer = make("mla", MLA_CFG + [("attn_impl", impl)])
    p = layer.init_params(jax.random.PRNGKey(3), [(E, S, 1)])
    x = x_node()

    def prog(p, x):
        return seq(layer.apply(p, {}, [x], ctx())[0][0])
    with jax.default_matmul_precision("highest"):
        close(prog(p, x), ref.attention(p, seq(x), C))
        g1 = jax.grad(lambda p, x: jnp.sum(prog(p, x) ** 2), (0, 1))(p, x)
        g2 = jax.grad(lambda p, x: jnp.sum(
            ref.attention(p, seq(x), C) ** 2), (0, 1))(p, x)
    tree_close(g1, g2, 5e-5)


def test_mla_publishes_the_kernels_tile_gauges():
    """As the net is built, from shapes: 2048 positions take blocks of
    1024 — three executed tiles of four, the diagonal's two taken by
    sub-tiles of 256 (10 of 16 each), the one below them whole."""
    from cxxnet_tpu.ops.attention import flash_tile_classes
    from cxxnet_tpu.telemetry.registry import get_registry
    layer = make("mla", MLA_CFG)
    layer.name = "mla_gauges"
    assert layer.infer_shapes([(E, 2048, 1)]) == [(E, 2048, 1)]
    read = lambda what: dict(
        (tuple(labels), child.value) for labels, child in
        get_registry().get("cxxnet_attn_" + what).samples())[
            ("mla_gauges",)]
    cls = flash_tile_classes(2048, 1024)
    assert (read("tiles_executed"), read("tiles_total")) == (3, 4)
    assert read("tiles_masked") == cls["edge"] == 2
    assert read("subtile") == cls["subtile"] == 256
    assert (cls["sub_plain"], cls["sub_masked"], cls["sub_skipped"]) == (
        12, 8, 12)
    assert read("pairs_multiplied_over_attended") == pytest.approx(
        cls["pairs_multiplied"] / cls["pairs_attended"])
    assert cls["pairs_multiplied"] == 1024 ** 2 + (
        cls["sub_plain"] + cls["sub_masked"]) * cls["subtile"] ** 2
    assert 1.0 < read("pairs_multiplied_over_attended") < 3 / 2


def test_mla_rotary_is_on_interleaved_pairs():
    """Against the reference's own two forms: the program agrees with
    the interleaved one, and the halves form is another function."""
    from cxxnet_tpu.ops.attention import rope, rope_interleaved
    x = jax.random.normal(jax.random.PRNGKey(4), (1, S, 2, 8))
    close(rope_interleaved(x, 32e6), ref.rotary(x, 32e6, True))
    close(rope(x, 32e6), ref.rotary(x, 32e6, False))
    assert np.max(np.abs(np.asarray(rope_interleaved(x, 32e6))
                         - np.asarray(rope(x, 32e6)))) > 0.1


def moe_apply(layer, p, bias, x, train=True):
    st = dict(layer.init_state([(E, S, 1)]), sel_bias=bias)
    (y,), new = layer.apply(p, st, [x], ctx(train))
    return seq(y), new


@pytest.mark.parametrize("bias_scale", [0.0, 0.2])
def test_moe_sigmoid_router_is_the_references(bias_scale):
    """Sigmoid scores, the bias in the choice only (a planted one moves
    the choice and never the gates), weights normalised over the chosen
    and scaled, the shared expert, the bias's update rule."""
    layer = moe_layer()
    p = layer.init_params(jax.random.PRNGKey(5), [(E, S, 1)])
    bias = bias_scale * jax.random.normal(jax.random.PRNGKey(6), (16,))
    x = x_node(7)
    with jax.default_matmul_precision("highest"):
        y, new = moe_apply(layer, p, bias, x)
        want, want_bias = ref.experts(p, bias, seq(x), C)
        close(y, want)
        close(new["sel_bias"], want_bias, 1e-7)
        g1 = jax.grad(lambda p, x: jnp.sum(
            moe_apply(layer, p, bias, x)[0] ** 2), (0, 1))(p, x)
        g2 = jax.grad(lambda p, x: jnp.sum(
            ref.experts(p, bias, seq(x), C)[0] ** 2), (0, 1))(p, x)
    tree_close(g1, g2, 5e-5)
    stats = dict(zip(__import__("cxxnet_tpu.layers.moe", fromlist=["x"])
                     .MOE_STATS, np.asarray(new["stats"])))
    assert stats["pairs_held"] == B * S * 3 and stats["pairs_dropped"] == 0
    assert stats["pairs_elsewhere"] == 0
    # eval leaves the bias alone
    _, kept = moe_apply(layer, p, bias, x, train=False)
    close(kept["sel_bias"], bias, 0)


def test_moe_bias_never_enters_the_gates_or_the_gradient():
    layer = moe_layer()
    p = layer.init_params(jax.random.PRNGKey(5), [(E, S, 1)])
    x = x_node(7)
    # a bias that keeps every choice (one constant) changes nothing
    y0, _ = moe_apply(layer, p, jnp.zeros((16,)), x)
    y1, _ = moe_apply(layer, p, jnp.full((16,), 0.3), x)
    close(y0, y1, 1e-7)
    g = jax.grad(lambda b: jnp.sum(moe_apply(layer, p, b, x)[0] ** 2))(
        0.1 * jnp.arange(16.0))
    assert float(jnp.max(jnp.abs(g))) == 0.0


@pytest.mark.parametrize("skew", ["one_expert", "half_empty"])
def test_no_pair_is_dropped_under_planted_skew(skew):
    """Every position to one expert (plus its two runners-up), or half
    the experts never chosen: the routers are planted through the bias,
    one compiled executable serves both and the balanced case, every held
    pair is computed and the output is the reference's."""
    layer = moe_layer(first=0, held=8)
    c = dict(C, n_routed_experts=8)
    p = layer.init_params(jax.random.PRNGKey(8), [(E, S, 1)])
    x = x_node(9)
    fn = jax.jit(lambda p, b, x: moe_apply(layer, p, b, x))
    planted = {"one_expert": jnp.zeros((16,)).at[jnp.array([2, 3, 5])]
               .set(5.0),
               "half_empty": jnp.asarray(np.where(np.arange(16) % 2 == 0,
                                                  5.0, 0.0), jnp.float32)}
    with jax.default_matmul_precision("highest"):
        for bias in (jnp.zeros((16,)), planted[skew]):
            y, new = fn(p, bias, x)
            close(y, ref.experts(p, bias, seq(x), c)[0])
            held, elsewhere, dropped = np.asarray(new["stats"])[:3]
            assert dropped == 0 and held + elsewhere == B * S * 3
        assert fn._cache_size() == 1
    if skew == "one_expert":
        assert held == B * S * 3        # all three chosen are held
        assert np.asarray(new["stats"])[3] > 5.0    # load max over mean


# -- the ladder of buffer sizes (PR 31) -----------------------------------------

#: experts 4 and 5 of 16 held, top-3 over 64 positions: a balanced share
#: of 24 pairs, so rungs of 48 and 96 rows under the 128 that every pair
#: the two can get fits in
LADDER = (48, 96, 128)


def _planted_bias(scores, n_held):
    """A selection bias under which exactly ``n_held`` of the 64 x 3
    pairs go to experts 4 and 5: expert 4 at every position or at none,
    expert 5 at the positions whose score is nearest the chosen."""
    everywhere = n_held >= B * S
    want = n_held - B * S if everywhere else n_held
    others = np.sort(np.delete(scores, [4, 5], axis=1), axis=1)
    # the score expert 5 has to pass: the others' second largest where
    # expert 4 takes a place, their third largest where it takes none
    need = np.sort(others[:, -2 if everywhere else -3] - scores[:, 5])
    edges = np.concatenate([[need[0] - 1.0], need, [need[-1] + 1.0]])
    bias = np.zeros(16, np.float32)
    bias[4] = 10.0 if everywhere else -10.0
    bias[5] = 0.5 * (edges[want] + edges[want + 1])
    return jnp.asarray(bias)


@pytest.fixture(scope="module")
def laddered():
    """One layer of three rungs, its jitted value-and-gradient, and the
    same layer held to its last rung."""
    from cxxnet_tpu.layers import moe
    layer = moe_layer(first=4, held=2)
    p = layer.init_params(jax.random.PRNGKey(15), [(E, S, 1)])
    x = x_node(16)
    assert moe.buffer_ladder(B * S, 3, 2, 16) == LADDER

    def jitted():       # a function of its own: jit caches by function
        def run(p, bias, x):
            def loss(p, x):
                y, new = moe_apply(layer, p, bias, x)
                return jnp.sum(y ** 2), (y, new["stats"])
            return jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x)
        return jax.jit(run)
    fn, last = jitted(), jitted()
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.sigmoid(
            seq(x).reshape(B * S, E) @ p["router"]["wmat"]))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe, "buffer_ladder", lambda *a: LADDER[-1:])
            last(p, jnp.zeros((16,)), x)        # traced on one rung
    return {"layer": layer, "p": p, "x": x, "fn": fn, "last": last,
            "scores": scores}


@pytest.mark.parametrize("n_held,rung", [
    (20, 48), (48, 48), (49, 96), (80, 96), (96, 96), (97, 128),
    (128, 128)])
def test_each_rung_is_the_last_rungs_and_the_references(laddered, n_held,
                                                        rung):
    """The routing planted through the bias so that each rung is taken
    in turn, the held pairs exactly a rung's rows and one more: output,
    input gradient and every weight's gradient (``g``, ``h``, ``o``, the
    router, the shared expert) are the plain reference's and the last
    rung's own, no pair is dropped, the rung is the smallest that holds
    the pairs, and one executable serves every case."""
    p, x = laddered["p"], laddered["x"]
    bias = _planted_bias(laddered["scores"], n_held)
    c = dict(C, n_routed_experts=2, expert_first=4)
    with jax.default_matmul_precision("highest"):
        (_, (y, stats)), grads = laddered["fn"](p, bias, x)
        (_, (y_last, stats_last)), grads_last = laddered["last"](p, bias, x)
        want = lambda p, x: ref.experts(p, bias, seq(x), c)[0]
        close(y, want(p, x))
        tree_close(grads, jax.grad(
            lambda p, x: jnp.sum(want(p, x) ** 2), (0, 1))(p, x), 5e-5)
    close(y, y_last, 1e-6)
    tree_close(grads, grads_last, 1e-6)
    assert laddered["fn"]._cache_size() == 1
    assert laddered["last"]._cache_size() == 1
    held, elsewhere, dropped, _, _, rows = np.asarray(stats)
    assert (held, elsewhere, dropped, rows) \
        == (n_held, B * S * 3 - n_held, 0, rung)
    assert np.asarray(stats_last)[5] == LADDER[-1]
    assert np.array_equal(np.asarray(stats)[:5], np.asarray(stats_last)[:5])


def _conditionals(jaxpr):
    """Every ``cond`` equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _conditionals(sub)


def _buffers_across(conds):
    """The conditionals' results that are as long as a rung of
    ``LADDER`` or as all the pairs: a buffer that left its branch."""
    sized = set(LADDER) | {B * S * 3}
    return [v.aval.shape for eqn in conds for v in eqn.outvars
            if v.aval.shape and v.aval.shape[0] in sized]


def test_no_buffer_of_a_rung_crosses_a_conditional(laddered):
    """Differentiated as it stands a ``lax.switch`` has every branch
    emit zeros for every other branch's residuals — the last rung's
    buffers on every step. Under the layer's ``jax.checkpoint`` the
    gradient's conditionals hand on nothing a rung sizes; the bare
    switch, differentiated, does (the check can see the trap)."""
    from cxxnet_tpu.layers import moe
    layer, p, x = laddered["layer"], laddered["p"], laddered["x"]
    bias = jnp.zeros((16,))
    loss = jax.checkpoint(
        lambda p, x: jnp.sum(moe_apply(layer, p, bias, x)[0] ** 2))
    conds = list(_conditionals(
        jax.make_jaxpr(jax.grad(loss, (0, 1)))(p, x).jaxpr))
    assert len(conds) >= 2          # the forward's and the backward's
    assert all(len(eqn.params["branches"]) == len(LADDER) for eqn in conds)
    assert _buffers_across(conds) == []

    def bare(xf, gate, w):
        order = jnp.arange(B * S * 3, dtype=jnp.int32)
        sizes = jnp.array([30, 30], jnp.int32)
        return jnp.sum(moe._on_rung(
            LADDER, jnp.sum(sizes), moe._rung_fwd, jnp.sum(sizes), xf, gate,
            order, order, sizes, *w) ** 2)
    w = [p[k]["wmat"] for k in "gho"]
    trapped = list(_conditionals(jax.make_jaxpr(jax.grad(bare, (0, 2)))(
        seq(x).reshape(B * S, E), jnp.ones((B * S, 3)), w).jaxpr))
    assert (LADDER[-1], E) in _buffers_across(trapped)


def test_a_ladder_of_one_rung_traces_no_conditional():
    """``held = X`` (2S reaches the room for every pair): the code is
    the one buffer's, forward and backward."""
    from cxxnet_tpu.layers import moe
    assert moe.buffer_ladder(B * S, 3, 16, 16) == (B * S * 3,)
    assert moe.buffer_ladder(B * S, 3, 8, 16) == (B * S * 3,)
    assert moe.buffer_ladder(8192, 8, 16, 256) \
        == (8192, 16384, 32768, 65536)
    layer = moe_layer()
    p = layer.init_params(jax.random.PRNGKey(5), [(E, S, 1)])
    text = str(jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(moe_apply(
        layer, p, jnp.zeros((16,)), x)[0] ** 2), (0, 1)))(p, x_node(7)))
    assert "cond[" not in text and "ragged_dot" in text


def test_the_rung_taken_reaches_the_registry(laddered, monkeypatch):
    """``Trainer._count_moe`` fed the six-entry ``stats``: the new gauge
    by layer, the steps on which a layer took its last rung, and the
    five older entries where they were (in a registry of the test's own:
    the process's is another test's to count in)."""
    import types
    from cxxnet_tpu.telemetry import registry
    from cxxnet_tpu.trainer import Trainer
    reg = registry.MetricRegistry()
    monkeypatch.setattr(registry, "get_registry", lambda: reg)
    me = types.SimpleNamespace(net=types.SimpleNamespace(
        layers=[laddered["layer"]]))
    counters = ["cxxnet_moe_pairs_held_total",
                "cxxnet_moe_pairs_elsewhere_total",
                "cxxnet_moe_pairs_dropped_total", "cxxnet_moe_steps_total",
                "cxxnet_moe_full_buffer_steps_total",
                "cxxnet_moe_pairs_held_last_step"]
    gauges = ["cxxnet_moe_buffer_rows", "cxxnet_moe_load_max_over_mean",
              "cxxnet_moe_sel_bias_absmax"]

    def read():
        return [reg.get(n).value for n in counters], \
            [dict(reg.get(n).samples())[("L",)].value for n in gauges]
    Trainer._count_moe(me, {"L": jnp.array([40., 152., 0., 1.5, .25, 48.])})
    assert read() == ([40., 152., 0., 1., 0., 40.], [48., 1.5, .25])
    Trainer._count_moe(me, {"L": jnp.array([100., 92., 0., 4., .5, 128.])})
    assert read() == ([140., 244., 0., 2., 1., 100.], [128., 4., .5])


def test_the_shares_add_up_to_the_whole_layer():
    """Four chips of four experts each: the routed parts all the shares
    give, with the shared expert — which every chip computes alike —
    counted once, are what the uncut reference gives for the whole layer,
    forward and input gradient."""
    whole = moe_layer()
    p = whole.init_params(jax.random.PRNGKey(10), [(E, S, 1)])
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(11), (16,))
    x = x_node(12)
    p_shared_off = dict(p, shared=jax.tree_util.tree_map(
        jnp.zeros_like, p["shared"]))

    def share(first, x, params):
        layer = moe_layer(first=first, held=4)
        cut = dict(params, **{k: {"wmat": params[k]["wmat"][first:first + 4]}
                              for k in ("g", "h", "o")})
        return moe_apply(layer, cut, bias, x)[0]

    def summed(x):
        routed = sum(share(f, x, p_shared_off) for f in (0, 4, 8, 12))
        shared_once = share(0, x, p) - share(0, x, p_shared_off)
        return routed + shared_once
    with jax.default_matmul_precision("highest"):
        want = lambda x: ref.experts(p, bias, seq(x), C)[0]
        close(summed(x), want(x))
        close(jax.grad(lambda x: jnp.sum(summed(x) ** 2))(x),
              jax.grad(lambda x: jnp.sum(want(x) ** 2))(x), 5e-5)


def test_capacity_router_still_refuses_topk_beyond_two():
    with pytest.raises(ValueError, match="topk must be 1 or 2"):
        make("moe", [("num_expert", "8"), ("topk", "3")])
    # and the capacity path keeps its state and parameter layout
    layer = make("moe", [("num_expert", "4"), ("topk", "2"),
                         ("nhidden", "16")])
    assert set(layer.init_state([(E, S, 1)])) == {"_aux_loss"}
    assert "bias" in layer.init_params(jax.random.PRNGKey(0),
                                       [(E, S, 1)])["h"]


def test_lmloss_shift_and_the_metric_reduced_on_the_device():
    from cxxnet_tpu.metrics import MetricSeqError, MetricSeqLogloss
    V = 11
    logits = jax.random.normal(jax.random.PRNGKey(13), (B, S, 1, V))
    label = jax.random.randint(jax.random.PRNGKey(14), (B, S), 0, V) \
        .astype(jnp.float32)
    mask = jnp.ones((B,))
    for shift in (0, 1):
        layer = create_layer(LayerSpec("lmloss", "L", [1], [1],
                                       [("shift", str(shift))]), [])
        out, _ = layer.apply({}, {}, [logits], ctx())
        lp = np.asarray(out[0]).reshape(B, S, V)
        lab = np.asarray(label, np.int64)
        n = S - shift
        picked = np.take_along_axis(lp[:, :n], lab[:, shift:, None], 2)[..., 0]
        close(layer.loss(out, label, mask), -picked.mean(), 1e-6)
        stats = np.asarray(layer.metric_stats(out, label))
        close(stats[:, 0], picked.sum(1), 1e-6)
        close(stats[:, 1], (lp[:, :n].argmax(2) == lab[:, shift:]).sum(1), 0)
        close(stats[:, 2], np.full(B, n), 0)
        if shift == 0:
            # the reduced form reads what the whole node reads
            for cls in (MetricSeqError, MetricSeqLogloss):
                whole, reduced = cls("m", "label"), cls("m", "label")
                whole.add(lp.reshape(B, -1), np.asarray(label))
                reduced.add_reduced(stats)
                assert whole.cnt == reduced.cnt
                close(whole.get(), reduced.get(), 1e-6)


# -- remat = 1 and the flash kernel's residuals (PR 29) ------------------------

def _toy_net(remat, attn_impl="flash"):
    """The toy ``joyai_llm_flash`` net (tests/benchmarks/data/joyai_toy)
    with its latent attention on the Pallas kernel."""
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.graph import build_graph
    from cxxnet_tpu.model import Network
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "joyai_toy",
                           "configs", "joyai_toy.conf")) as f:
        text = f.read()
    assert "remat = 1\n" in text and text.count("= mla:") == 3
    text = text.replace("remat = 1\n", f"remat = {remat}\n").replace(
        "  v_head_dim = 16\n",
        f"  v_head_dim = 16\n  attn_impl = {attn_impl}\n")
    cfg = parse_config_string(text + "batch_size = 2\n")
    return Network(build_graph(cfg), cfg)


def _toy_loss(net, state, data, label):
    return lambda p: net.apply(p, state, data, label=label,
                               mask=jnp.ones((2,)), train=True).loss


def test_toy_net_under_remat_is_the_plain_net_and_runs_each_kernel_once():
    """Loss and every leaf's gradient under ``remat = 1`` are those
    under ``remat = 0`` with ``attn_impl = flash``, and the rebuilt
    layers hold no second run of the kernel's forward: three attention
    layers, three forward kernels, three backward kernels."""
    plain, remat = _toy_net(0), _toy_net(1)
    params, state = plain.init(jax.random.PRNGKey(3))
    toks = np.random.RandomState(3).randint(0, 64, (2, 32))
    data = jnp.asarray(toks.reshape(2, 1, 1, 32), jnp.float32)
    label = jnp.asarray((toks + toks[:, :1]) % 64, jnp.float32)
    l0, g0 = jax.value_and_grad(_toy_loss(plain, state, data, label))(params)
    l1, g1 = jax.value_and_grad(_toy_loss(remat, state, data, label))(params)
    close(l1, l0, 1e-6)
    assert jax.tree_util.tree_structure(g0) == \
        jax.tree_util.tree_structure(g1)
    tree_close(g1, g0, 1e-5)
    text = str(jax.make_jaxpr(jax.grad(
        _toy_loss(remat, state, data, label)))(params))
    assert (text.count("name=flash_fwd"), text.count("name=flash_bwd")) \
        == (3, 3)


def test_a_net_without_the_kernel_keeps_nothing_under_remat(monkeypatch):
    """``remat = 1`` saves what the flash kernel's forward names and
    nothing else: a net that never reaches the kernel lowers to the
    program it lowered to under the bare ``jax.checkpoint``."""
    remat = _toy_net(1, attn_impl="ref")
    params, state = remat.init(jax.random.PRNGKey(3))
    data = jnp.zeros((2, 1, 1, 32), jnp.float32)
    label = jnp.ones((2, 32), jnp.float32)
    lower = lambda: jax.jit(jax.grad(_toy_loss(
        remat, state, data, label))).lower(params).as_text()
    kept = lower()
    bare = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint",
                        lambda f, policy=None: bare(f))
    assert lower() == kept
    assert "flash" not in kept
