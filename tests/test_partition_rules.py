"""Rule-driven sharding (parallel/rules.py + Network.partition_rules).

Pins the ISSUE-9 contracts: every param AND optimizer-state leaf of
every example model matches exactly one partition rule (unmatched
leaves fail loudly with their tree path), the rule-derived specs equal
the legacy per-layer declarations, config ``partition_rules`` entries
override the generated table (and flow into the manual-tp plan), and a
dp-width-change reshard round-trips optimizer state losslessly through
the shard/gather fns.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "examples", "ImageNet"))

from cxxnet_tpu.config import (ConfigError, parse_config_file,
                               parse_config_string, parse_sharding_config)
from cxxnet_tpu.graph import build_graph
from cxxnet_tpu.model import Network
from cxxnet_tpu.optim import create_optimizer
from cxxnet_tpu.parallel import make_mesh_context
from cxxnet_tpu.parallel.rules import (UnmatchedLeafError, add_fsdp,
                                       make_shard_and_gather_fns,
                                       match_partition_rules,
                                       parse_rule_string, rule_coverage,
                                       tree_paths)

EXAMPLES = os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "examples")

LM_CFG = """
netconfig=start
layer[+1:e0] = embed:tok_embed
  nhidden = 32
  vocab_size = 16
layer[+1:n1] = layernorm:ln1
layer[+1:a1] = mha:attn1
  nhead = 4
  causal = 1
layer[e0,a1->r1] = add:res1
layer[+1:n2] = layernorm:ln2
layer[+1:f1] = moe:moe1
  num_expert = 4
  topk = 2
  nhidden = 64
layer[r1,f1->r2] = add:res2
layer[+1:lg] = seqfc:lm_head
  nhidden = 16
layer[+0] = lmloss
netconfig=end
input_shape = 1,1,32
label_vec[0,32) = label
batch_size = 8
updater = adam
"""


def _ibn_cfg():
    from gen_inception_bn import generate
    return parse_config_string(generate(scale=0.25, image_size=64,
                                        num_class=8, batch_size=8,
                                        with_data=False))


def _nets():
    """(name, Network, updater) for the three example model families."""
    mnist = parse_config_file(
        os.path.join(EXAMPLES, "MNIST", "mnist_lenet.conf"))
    lm = parse_config_string(LM_CFG)
    ibn = _ibn_cfg()
    out = []
    for name, cfg, upd in (("mnist", mnist, "sgd"), ("ibn", ibn, "sgd"),
                           ("lm", lm, "adam")):
        out.append((name, Network(build_graph(cfg), cfg), cfg, upd))
    return out


@pytest.mark.quick
def test_rule_coverage_params_and_opt_state():
    """Every non-scalar param AND optimizer-state leaf of MNIST,
    Inception-BN and the LM matches EXACTLY one rule of its model's
    generated table."""
    for name, net, cfg, upd in _nets():
        rules = net.partition_rules()
        params = net.param_shapes()
        opt = create_optimizer(upd, cfg)
        state_shapes = jax.eval_shape(
            lambda p=params: opt.init_state(
                jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), p)))
        for tree in (params, state_shapes):
            cov = rule_coverage(rules, tree)
            assert cov, name
            bad = {path: idx for path, idx in cov.items()
                   if len(idx) != 1}
            assert not bad, (name, bad)
        # and the matcher agrees: produces a spec for every leaf
        specs = match_partition_rules(rules, state_shapes)
        n_specs = len(jax.tree_util.tree_leaves(
            specs, is_leaf=lambda v: isinstance(v, P)))
        n_leaves = len(jax.tree_util.tree_leaves(state_shapes))
        assert n_specs == n_leaves


@pytest.mark.quick
def test_unmatched_leaf_fails_with_path():
    tree = {"conv1": {"wmat": jnp.zeros((4, 4))},
            "mystery": {"weird": jnp.zeros((3, 3))}}
    rules = [(r"(^|/)conv1/wmat$", P())]
    with pytest.raises(UnmatchedLeafError) as e:
        match_partition_rules(rules, tree)
    assert "mystery/weird" in str(e.value)


@pytest.mark.quick
def test_rules_match_legacy_layer_pspecs():
    """Acceptance: the rule-derived specs equal the per-layer
    ``layer.param_pspecs()`` declarations for the existing models
    (replicated-by-omission == explicit P())."""
    for name, net, _cfg, _upd in _nets():
        derived = net.param_pspecs()
        for spec, layer in zip(net.graph.layers, net.layers):
            if spec.is_shared or not layer.has_params:
                continue
            declared = dict(tree_paths(
                layer.param_pspecs() or {},
                is_leaf=lambda v: isinstance(v, tuple))[0])
            got = dict(tree_paths(
                derived[layer.name],
                is_leaf=lambda v: isinstance(v, tuple))[0])
            for path, spec_got in got.items():
                want = declared.get(path)
                assert tuple(spec_got) == tuple(want or ()), (
                    name, layer.name, path, spec_got, want)


@pytest.mark.quick
def test_config_rules_override_and_flow_into_manual_plan():
    """A ``partition_rules`` config entry overrides the generated table
    (first match wins) AND changes the derived manual-tp plan — the
    manual-tp execution plan follows the same declarative source."""
    cfg = parse_config_string(LM_CFG)
    net = Network(build_graph(cfg), cfg)
    assert tuple(net.param_pspecs()["lm_head"]["wmat"]) == (None, "model")
    cfg2 = parse_config_string(
        LM_CFG + 'partition_rules = "lm_head/wmat->-"\n')
    net2 = Network(build_graph(cfg2), cfg2)
    # '-' = one unsharded dim: replicated (no named axis survives)
    assert all(ax is None for ax in net2.param_pspecs()["lm_head"]["wmat"])
    # manual plan: the overridden layer drops out of the tp plan
    ibn = _ibn_cfg()
    netA = Network(build_graph(ibn), ibn)
    planned = {netA.graph.layers[li].name
               for li, ent in netA.tp_manual_plan(2).items()
               if "params" in ent
               # producers (rule-driven slice), not tp_follow riders
               and getattr(netA.layers[li], "tp_manual_axis", None)
               is not None}
    victim = sorted(planned)[0]
    ibn2 = ibn + [("partition_rules", f"{victim}/->-")]
    netB = Network(build_graph(ibn2), ibn2)
    plannedB = {netB.graph.layers[li].name
                for li, ent in netB.tp_manual_plan(2).items()
                if "params" in ent}
    assert victim in planned and victim not in plannedB


@pytest.mark.quick
def test_generated_anchors_do_not_cross_match_nested_leaves():
    """A layer named 'o' must not capture another layer's nested
    'attn1/o/wmat' leaf via suffix matching — generated anchors admit
    only the optimizer-state prefixes (mom/m1/m2)."""
    cfg = parse_config_string("""
netconfig=start
layer[+1:e0] = embed:tok_embed
  nhidden = 32
  vocab_size = 16
layer[+1:o1] = seqfc:o
  nhidden = 32
layer[+1:a1] = mha:attn1
  nhead = 4
layer[+1:lg] = seqfc:lm_head
  nhidden = 16
layer[+0] = lmloss
netconfig=end
input_shape = 1,1,16
label_vec[0,16) = label
batch_size = 8
""")
    net = Network(build_graph(cfg), cfg)
    specs = net.param_pspecs()
    # fullc 'o' is (in, out)-sharded P(None, 'model'); mha's o-proj is
    # (h, d, e) with spec ('model', None, None) — a suffix cross-match
    # would hand the 2-dim fullc spec to the 3-dim attention leaf
    assert tuple(specs["o"]["wmat"]) == (None, "model")
    assert tuple(specs["attn1"]["o"]["wmat"]) == ("model", None, None)
    # the optimizer-state mirror still matches through its prefix
    from cxxnet_tpu.parallel.rules import match_partition_rules
    m = match_partition_rules(net.partition_rules(),
                              {"mom": net.param_shapes()})
    assert tuple(m["mom"]["attn1"]["o"]["wmat"]) == ("model", None, None)


@pytest.mark.quick
def test_parse_rule_string():
    rules = parse_rule_string("a/wmat->-,model; b/.*-> ;c->data,-,-")
    assert rules[0] == ("a/wmat", P(None, "model"))
    assert rules[1] == ("b/.*", P())
    assert rules[2] == ("c", P("data", None, None))
    with pytest.raises(ValueError):
        parse_rule_string("no_arrow_here")
    with pytest.raises(ValueError):
        parse_rule_string("ba[d->model")


@pytest.mark.quick
def test_sharding_config_namespace_validation():
    """Satellite: typo'd keys in the sharding namespace raise instead
    of being ignored; values are validated."""
    ok = parse_sharding_config([("fsdp_axis", "data"),
                                ("fsdp_min_size", "64")])
    assert ok.fsdp_axis == "data" and ok.fsdp_min_size == 64
    with pytest.raises(ConfigError):
        parse_sharding_config([("fsdp_axes", "data")])       # typo
    with pytest.raises(ConfigError):
        parse_sharding_config([("partition_ruless", "x->-")])  # typo
    with pytest.raises(ConfigError):
        parse_sharding_config([("fsdp_axis", "bogus")])
    with pytest.raises(ConfigError):
        parse_sharding_config([("fsdp_min_size", "not_an_int")])
    with pytest.raises(ConfigError):
        parse_sharding_config([("partition_rules", "broken[->model")])


def test_reshard_roundtrip_opt_state_across_dp_widths():
    """Acceptance: a dp-width change (8 -> 4 devices) round-trips
    optimizer state through the gather/shard fns losslessly — the
    elastic-training reshard primitive (ROADMAP item 4)."""
    cfg = parse_config_string(LM_CFG)
    net = Network(build_graph(cfg), cfg)
    params, _ = net.init(jax.random.PRNGKey(0))
    opt = create_optimizer("adam", cfg)
    state = opt.init_state(params)
    # fill the moments with recognizable values
    state["m1"] = jax.tree_util.tree_map(
        lambda x: x + np.float32(0.125), state["m1"])
    host0 = jax.tree_util.tree_map(np.asarray, state)

    def specs_for(net_, ctx, width):
        base = match_partition_rules(net_.partition_rules(),
                                     {"m1": net_.param_shapes(),
                                      "m2": net_.param_shapes(),
                                      "t": jax.ShapeDtypeStruct(
                                          (), jnp.int32)})
        return add_fsdp(base, {"m1": net_.param_shapes(),
                               "m2": net_.param_shapes(),
                               "t": jax.ShapeDtypeStruct((), jnp.int32)},
                        "data", width, min_size=16)

    ctx8 = make_mesh_context(devices=jax.devices()[:8])
    shard8, gather8 = make_shard_and_gather_fns(
        ctx8, specs_for(net, ctx8, 8))
    sharded8 = shard8(state)
    # at least one big leaf actually sharded over dp
    m1w = sharded8["m1"]["attn1"]["q"]["wmat"]
    assert not m1w.sharding.is_fully_replicated
    back8 = jax.tree_util.tree_map(np.asarray, gather8(sharded8))

    ctx4 = make_mesh_context(devices=jax.devices()[:4])
    shard4, gather4 = make_shard_and_gather_fns(
        ctx4, specs_for(net, ctx4, 4))
    back4 = jax.tree_util.tree_map(np.asarray, gather4(shard4(back8)))
    flat0, _ = jax.tree_util.tree_flatten(host0)
    flat4, _ = jax.tree_util.tree_flatten(back4)
    for a, b in zip(flat0, flat4):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_reshard_roundtrip_dp_4_2_4_with_fp16_scaler_and_meta():
    """Satellite (ISSUE 10): the elastic reshard round-trip must cover
    the WHOLE training state, not just plain param/opt leaves — the
    fp16 loss-scaler subtree (``opt_state["_mp"]``: fp32 scale + int32
    clean-step counter) rides the reshard across dp 4 -> 2 -> 4
    bit-exactly, and the checkpoint meta's ``lr_scale``/``step_count``
    survive a cross-width save/restore."""
    import tempfile

    from cxxnet_tpu import checkpoint as ckpt
    from cxxnet_tpu.elastic import reshard_tree
    from cxxnet_tpu.trainer import Trainer

    fp16 = [("compute_dtype", "float16")]
    cfg = parse_config_string(LM_CFG) + fp16
    net = Network(build_graph(cfg), cfg)
    params, _ = net.init(jax.random.PRNGKey(0))
    opt = create_optimizer("adam", cfg)
    assert opt.fp16
    state = opt.init_state(params)
    assert "_mp" in state
    # recognizable, non-default scaler state: a round-trip that
    # silently re-inits the subtree would be caught
    state["_mp"] = {"scale": jnp.float32(1024.0),
                    "good": jnp.int32(37)}
    host0 = jax.tree_util.tree_map(np.asarray, state)

    def specs_for(ctx, width):
        shapes = jax.eval_shape(lambda: state)
        base = match_partition_rules(net.partition_rules(), shapes)
        return add_fsdp(base, shapes, "data", width, min_size=16)

    ctx4 = make_mesh_context(devices=jax.devices()[:4])
    ctx2 = make_mesh_context(devices=jax.devices()[:2])
    # scalars ("_mp", "t") must spec as replicated P() via the scalar
    # rule — never partitioned
    s4 = specs_for(ctx4, 4)
    assert tuple(s4["_mp"]["scale"]) == () and tuple(s4["t"]) == ()
    mid = reshard_tree(state, ctx4, ctx2, s4, specs_for(ctx2, 2))
    # at least one big leaf is genuinely dp-sharded at each width
    assert not mid["m1"]["attn1"]["q"]["wmat"].sharding \
        .is_fully_replicated
    back = reshard_tree(mid, ctx2, ctx4, specs_for(ctx2, 2), s4)
    flat0, _ = jax.tree_util.tree_flatten(host0)
    flat4, _ = jax.tree_util.tree_flatten(
        jax.tree_util.tree_map(np.asarray, ctx4.gather(back)))
    for a, b in zip(flat0, flat4):
        assert a.dtype == b.dtype and np.array_equal(a, b)

    # cross-width checkpoint: save from a dp=4 fp16 trainer, restore
    # onto dp=2 — _mp, lr_scale and step_count all carried
    tr_cfg = parse_config_string("""
netconfig=start
layer[0->1] = fullc:fc_big
  nhidden = 64
  init_sigma = 0.01
layer[1->2] = relu:r1
layer[2->3] = fullc:fc_out
  nhidden = 4
  init_sigma = 0.01
layer[3->3] = softmax
netconfig=end
input_shape = 1,1,32
batch_size = 8
eta = 0.1
eval_train = 0
compute_dtype = float16
""")
    from cxxnet_tpu.io.data import DataBatch
    tr4 = Trainer(tr_cfg, mesh_ctx=ctx4)
    tr4.init_model()
    rng = np.random.RandomState(0)
    for _ in range(3):
        tr4.update(DataBatch(
            data=rng.randn(8, 1, 1, 32).astype(np.float32),
            label=rng.randint(0, 4, (8, 1)).astype(np.float32)))
    tr4.optimizer.lr_scale = 0.125
    with tempfile.TemporaryDirectory() as td:
        path = ckpt.model_path(td, 0)
        tr4.save_model(path)
        tr2 = Trainer(tr_cfg, mesh_ctx=ctx2)
        tr2.load_model(path)
    assert tr2._step_count == 3
    assert tr2.optimizer.lr_scale == 0.125
    mp4 = jax.tree_util.tree_map(np.asarray, tr4.opt_state["_mp"])
    mp2 = jax.tree_util.tree_map(np.asarray, tr2.opt_state["_mp"])
    assert mp4["scale"] == mp2["scale"] and mp4["good"] == mp2["good"]
    for a, b in zip(
            jax.tree_util.tree_leaves(ckpt.jax_to_numpy(
                tr4.mesh.gather(tr4.opt_state))),
            jax.tree_util.tree_leaves(ckpt.jax_to_numpy(tr2.opt_state))):
        assert np.array_equal(a, b)


def test_fsdp_trainer_placement_and_parity():
    """fsdp_axis = data: params + optimizer state shard at rest over
    the data axis on the std path, and the 2-step trajectory matches
    the replicated run exactly (placement, not math)."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    base = parse_config_string("""
netconfig=start
layer[0->1] = fullc:fc_big
  nhidden = 64
  init_sigma = 0.01
layer[1->2] = relu:r1
layer[2->3] = fullc:fc_out
  nhidden = 4
  init_sigma = 0.01
layer[3->3] = softmax
netconfig=end
input_shape = 1,1,32
batch_size = 8
eta = 0.1
eval_train = 0
""")
    rng = np.random.RandomState(0)
    data = rng.randn(8, 1, 1, 32).astype(np.float32)
    label = rng.randint(0, 4, (8, 1)).astype(np.float32)

    def run(extra):
        tr = Trainer(base + extra,
                     mesh_ctx=make_mesh_context(devices=jax.devices()[:8]))
        tr.init_model()
        losses = []
        for _ in range(2):
            from cxxnet_tpu.io.data import DataBatch as DB
            tr.update(DB(data=data.copy(), label=label.copy()))
            losses.append(float(tr.last_loss))
        return tr, losses

    tr_f, loss_f = run([("fsdp_axis", "data"), ("fsdp_min_size", "64")])
    w = tr_f.params["fc_big"]["wmat"]
    assert not w.sharding.is_fully_replicated
    m = tr_f.opt_state["mom"]["fc_big"]["wmat"]
    assert not m.sharding.is_fully_replicated
    tr_r, loss_r = run([])
    for a, b in zip(loss_f, loss_r):
        assert abs(a - b) < 1e-5, (loss_f, loss_r)
    # and sp/pp reject the knob loudly
    with pytest.raises(ValueError):
        Trainer(base + [("fsdp_axis", "data"),
                        ("pipeline_parallel", "2"), ("stage", "0")],
                mesh_ctx=make_mesh_context(devices=jax.devices()[:2],
                                           pipeline_parallel=2))
