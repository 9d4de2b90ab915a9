"""Per-layer golden tests vs numpy references — this framework's equivalent of
the reference's runtime PairTest harness (SURVEY §4.1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.config import parse_config_string
from cxxnet_tpu.graph import build_graph
from cxxnet_tpu.model import Network


def make_net(body: str, input_shape="1,1,16", extra=""):
    text = f"""
netconfig=start
{body}
netconfig=end
input_shape = {input_shape}
{extra}
"""
    g = build_graph(parse_config_string(text))
    return Network(g, g.defcfg)


def run(net, x, train=False, label=None, rng=None):
    params, state = net.init(jax.random.PRNGKey(0))
    res = net.apply(params, state, jnp.asarray(x), label=label, rng=rng,
                    train=train, capture_nodes=True)
    return params, res


def test_fullc_forward():
    net = make_net("layer[+1:h] = fullc:fc1\n  nhidden = 8")
    x = np.random.RandomState(0).randn(4, 1, 1, 16).astype(np.float32)
    params, res = run(net, x)
    w = np.asarray(params["fc1"]["wmat"])
    b = np.asarray(params["fc1"]["bias"])
    expect = x.reshape(4, 16) @ w + b
    np.testing.assert_allclose(np.asarray(res.out).reshape(4, 8), expect,
                               rtol=1e-5)


def test_fullc_no_bias_and_init_uniform():
    net = make_net(
        "layer[+1:h] = fullc:fc1\n  nhidden = 8\n  no_bias = 1\n"
        "  random_type = xavier\n  init_uniform = 0.2")
    params, _ = run(net, np.zeros((2, 1, 1, 16), np.float32))
    assert "bias" not in params["fc1"]
    w = np.asarray(params["fc1"]["wmat"])
    assert np.abs(w).max() <= 0.2


def test_activations():
    for name, fn in [("relu", lambda v: np.maximum(v, 0)),
                     ("sigmoid", lambda v: 1 / (1 + np.exp(-v))),
                     ("tanh", np.tanh)]:
        net = make_net(f"layer[+1] = {name}")
        x = np.random.RandomState(1).randn(3, 1, 1, 16).astype(np.float32)
        _, res = run(net, x)
        np.testing.assert_allclose(np.asarray(res.out), fn(x).reshape(3, 1, 1, 16),
                                   rtol=1e-5, atol=1e-6)


def test_conv_shape_and_groups():
    net = make_net(
        "layer[0->1] = conv:cv\n  kernel_size = 3\n  stride = 2\n  pad = 1\n"
        "  nchannel = 8\n  ngroup = 2", input_shape="4,13,13")
    # floor mode: (13 + 2 - 3)//2 + 1 = 7
    assert net.node_shapes[1] == (8, 7, 7)
    x = np.random.RandomState(2).randn(2, 13, 13, 4).astype(np.float32)
    _, res = run(net, x)
    assert res.out.shape == (2, 7, 7, 8)


def test_conv_vs_numpy():
    net = make_net("layer[0->1] = conv:cv\n  kernel_size = 2\n  nchannel = 3",
                   input_shape="2,4,4")
    x = np.random.RandomState(3).randn(1, 4, 4, 2).astype(np.float32)
    params, res = run(net, x)
    w = np.asarray(params["cv"]["wmat"])  # (2,2,2,3) HWIO
    b = np.asarray(params["cv"]["bias"])
    out = np.zeros((1, 3, 3, 3), np.float32)
    for oy in range(3):
        for ox in range(3):
            patch = x[0, oy:oy + 2, ox:ox + 2, :]      # (2,2,2)
            out[0, oy, ox, :] = np.einsum("hwi,hwio->o", patch, w) + b
    np.testing.assert_allclose(np.asarray(res.out), out, rtol=1e-4, atol=1e-5)


def test_pooling_ceil_mode_shape():
    # reference formula: min(in+2p-k+s-1, in+2p-1)//s + 1
    # in=13, k=3, s=2, p=0 -> min(13-3+1, 12)//2+1 = 11//2+1 = 6 (ceil mode)
    net = make_net("layer[0->1] = max_pooling\n  kernel_size = 3\n  stride = 2",
                   input_shape="2,13,13")
    assert net.node_shapes[1] == (2, 6, 6)
    x = np.random.RandomState(4).randn(2, 13, 13, 2).astype(np.float32)
    _, res = run(net, x)
    assert res.out.shape == (2, 6, 6, 2)
    # last window is truncated: covers rows 10..12
    expect = x[:, 10:13, 10:13, :].max(axis=(1, 2))
    np.testing.assert_allclose(np.asarray(res.out)[:, 5, 5, :], expect, rtol=1e-6)


def test_avg_pooling_counts_padding():
    net = make_net("layer[0->1] = avg_pooling\n  kernel_size = 2\n  stride = 2",
                   input_shape="1,4,4")
    x = np.arange(16, dtype=np.float32).reshape(1, 4, 4, 1)
    _, res = run(net, x)
    expect = x.reshape(1, 2, 2, 2, 2, 1).mean(axis=(2, 4))
    np.testing.assert_allclose(np.asarray(res.out), expect, rtol=1e-6)


def test_relu_max_pooling_clamps_before_the_max():
    net = make_net("layer[0->1] = relu_max_pooling\n  kernel_size = 2\n  stride = 2",
                   input_shape="1,4,4")
    x = -np.ones((1, 4, 4, 1), np.float32)
    _, res = run(net, x)
    np.testing.assert_allclose(np.asarray(res.out), 0.0)


def test_flatten_then_fullc():
    net = make_net(
        "layer[0->1] = flatten\nlayer[1->2] = fullc:fc\n  nhidden = 5",
        input_shape="3,4,4")
    x = np.random.RandomState(5).randn(2, 4, 4, 3).astype(np.float32)
    _, res = run(net, x)
    assert res.out.shape == (2, 1, 1, 5)


def test_dropout_train_vs_eval():
    net = make_net("layer[+1:d] = flatten\nlayer[+0] = dropout\n  threshold = 0.5",
                   input_shape="1,1,1000")
    x = np.ones((2, 1, 1, 1000), np.float32)
    _, res_eval = run(net, x, train=False)
    np.testing.assert_allclose(np.asarray(res_eval.out), 1.0)
    _, res_train = run(net, x, train=True, rng=jax.random.PRNGKey(1))
    arr = np.asarray(res_train.out)
    assert set(np.unique(arr)).issubset({0.0, 2.0})
    assert 0.4 < (arr == 0).mean() < 0.6


def test_batch_norm_train_stats():
    net = make_net("layer[0->1] = batch_norm", input_shape="4,6,6")
    x = (np.random.RandomState(6).randn(8, 6, 6, 4) * 3 + 2).astype(np.float32)
    _, res = run(net, x, train=True)
    out = np.asarray(res.out)
    np.testing.assert_allclose(out.mean(axis=(0, 1, 2)), 0.0, atol=1e-4)
    np.testing.assert_allclose(out.std(axis=(0, 1, 2)), 1.0, atol=1e-3)
    # running stats updated: (1-momentum) * batch stats with zero init
    bn_name = net.graph.layers[0].name
    st = res.state[bn_name]
    np.testing.assert_allclose(np.asarray(st["running_exp"]),
                               0.1 * x.mean(axis=(0, 1, 2)), rtol=1e-3)


def test_plugin_layer(tmp_path, monkeypatch, mesh8):
    """User-plugin layers (the Caffe-adapter plugin analog,
    reference src/plugin/caffe_adapter-inl.hpp): a Layer subclass from a
    user module participates in the dialect graph, inits params, trains,
    and checkpoint-roundtrips like a built-in."""
    (tmp_path / "my_layers.py").write_text("""
import jax.numpy as jnp
from cxxnet_tpu.layers.base import Layer

class ScaledSwish(Layer):
    has_params = True

    def set_param(self, name, val):
        if name == "init_gain":
            self.init_gain = float(val)

    def __init__(self, spec, global_cfg):
        self.init_gain = 1.0
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        return {"wmat": jnp.full((1,), self.init_gain, jnp.float32)}

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]
        return [params["wmat"] * x * jnp.tanh(jnp.exp(x * 0.5) /
                                              (1 + jnp.exp(x * 0.5)))], state
""")
    monkeypatch.syspath_prepend(str(tmp_path))
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.trainer import Trainer
    from cxxnet_tpu.io.data import DataBatch
    cfg = parse_config_string("""
netconfig=start
layer[+1:h1] = fullc:fc1
  nhidden = 16
  random_type = xavier
layer[+1:a1] = plugin:act
  plugin_module = my_layers
  plugin_layer = ScaledSwish
  init_gain = 1.5
layer[+1:o] = fullc:fc2
  nhidden = 3
  random_type = xavier
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.2
eval_train = 0
""")
    tr = Trainer(cfg, mesh_ctx=mesh8)
    tr.init_model()
    assert float(tr.get_weight("act", "wmat")[0]) == 1.5
    rng = np.random.RandomState(0)
    b = DataBatch(data=rng.randn(16, 1, 1, 8).astype(np.float32),
                  label=rng.randint(0, 3, (16, 1)).astype(np.float32))
    tr.update(b)
    l0 = tr.last_loss
    for _ in range(8):
        tr.update(b)
    assert np.isfinite(tr.last_loss) and tr.last_loss < l0
    # the plugin's param trains too
    assert float(tr.get_weight("act", "wmat")[0]) != 1.5
    # clear errors for broken plugin configs
    from cxxnet_tpu.graph import build_graph
    from cxxnet_tpu.model import Network
    bad = parse_config_string("""
netconfig=start
layer[+1:a1] = plugin:p
  plugin_module = no_such_module_xyz
  plugin_layer = Nope
netconfig=end
input_shape = 1,1,8
""")
    with pytest.raises(ValueError, match="cannot import"):
        Network(build_graph(bad), bad)


def test_batch_norm_sync(mesh8):
    """Pins the documented sync-BN semantics (layers/norm.py): with the
    batch sharded over 8 devices, training stats reduce over the GLOBAL
    batch, not each device's local slice — running_exp after one step must
    match the full-batch mean, which differs per-shard by construction."""
    from cxxnet_tpu.trainer import Trainer
    from cxxnet_tpu.io.data import DataBatch
    cfg = parse_config_string("""
netconfig=start
layer[+1:b1] = batch_norm:bn
layer[+1:o] = fullc:fc
  nhidden = 2
layer[+0] = softmax
netconfig=end
input_shape = 1,1,4
batch_size = 64
eta = 0.0
metric = error
eval_train = 0
""")
    tr = Trainer(cfg, mesh_ctx=mesh8)
    tr.init_model()
    # row i has value i in every feature: each device shard of 8 rows has a
    # different local mean (3.5, 11.5, ...), global mean = 31.5
    x = np.tile(np.arange(64, dtype=np.float32)[:, None, None, None],
                (1, 1, 1, 4))
    b = DataBatch(data=x, label=np.zeros((64, 1), np.float32))
    tr.update(b)
    running = np.asarray(tr.net_state["bn"]["running_exp"])
    np.testing.assert_allclose(running, 0.1 * 31.5 * np.ones(4), rtol=1e-4)


def test_batch_norm_no_ma_eval_uses_batch_stats():
    net = make_net("layer[0->1] = batch_norm_no_ma", input_shape="4,6,6")
    x = (np.random.RandomState(7).randn(8, 6, 6, 4) * 3 + 2).astype(np.float32)
    _, res = run(net, x, train=False)
    out = np.asarray(res.out)
    np.testing.assert_allclose(out.mean(axis=(0, 1, 2)), 0.0, atol=1e-4)


def test_lrn_identity_when_alpha_zero():
    net = make_net("layer[0->1] = lrn\n  alpha = 0\n  local_size = 5",
                   input_shape="8,4,4")
    x = np.random.RandomState(8).randn(2, 4, 4, 8).astype(np.float32)
    _, res = run(net, x)
    np.testing.assert_allclose(np.asarray(res.out), x, rtol=1e-5)


def test_lrn_vs_numpy():
    net = make_net(
        "layer[0->1] = lrn\n  alpha = 0.001\n  beta = 0.75\n  local_size = 3",
        input_shape="6,2,2")
    x = np.random.RandomState(9).randn(1, 2, 2, 6).astype(np.float32)
    _, res = run(net, x)
    sq = x ** 2
    out = np.zeros_like(x)
    for c in range(6):
        lo, hi = max(0, c - 1), min(6, c + 2)
        norm = 1.0 + (0.001 / 3) * sq[..., lo:hi].sum(-1)
        out[..., c] = x[..., c] * norm ** -0.75
    np.testing.assert_allclose(np.asarray(res.out), out, rtol=1e-4)


def test_concat_and_split():
    net = make_net("""layer[0->a,b] = split
layer[a->c] = fullc:f1
  nhidden = 3
layer[b->d] = fullc:f2
  nhidden = 4
layer[c,d->e] = concat""")
    x = np.random.RandomState(10).randn(2, 1, 1, 16).astype(np.float32)
    _, res = run(net, x)
    assert res.out.shape == (2, 1, 1, 7)


def test_ch_concat():
    net = make_net("""layer[0->a] = conv:c1
  kernel_size = 1
  nchannel = 3
layer[0->b] = conv:c2
  kernel_size = 1
  nchannel = 5
layer[a,b->c] = ch_concat""", input_shape="2,4,4")
    assert net.node_shapes[net.graph.node_index("c")] == (8, 4, 4)


def test_xelu_prelu_insanity():
    x = np.random.RandomState(11).randn(4, 1, 1, 16).astype(np.float32)
    net = make_net("layer[+1] = xelu\n  b = 4")
    _, res = run(net, x)
    np.testing.assert_allclose(np.asarray(res.out),
                               np.where(x > 0, x, x / 4).reshape(4, 1, 1, 16),
                               rtol=1e-5)
    net = make_net("layer[+1] = prelu\n  init_slope = 0.25")
    params, res = run(net, x)
    np.testing.assert_allclose(
        np.asarray(res.out), np.where(x > 0, x, 0.25 * x).reshape(4, 1, 1, 16),
        rtol=1e-5)
    net = make_net("layer[+1] = insanity\n  lb = 4\n  ub = 8")
    _, res = run(net, x)  # eval mode: slope = (8-4)/(log8-log4)
    s = (8 - 4) / (np.log(8) - np.log(4))
    np.testing.assert_allclose(np.asarray(res.out),
                               np.where(x > 0, x, x / s).reshape(4, 1, 1, 16),
                               rtol=1e-5)
    # train mode: random slopes within [lb, ub]
    _, res = run(net, x, train=True, rng=jax.random.PRNGKey(2))
    arr = np.asarray(res.out).reshape(4, 16)
    neg = x.reshape(4, 16) < 0
    ratio = x.reshape(4, 16)[neg] / arr[neg]
    assert np.all(ratio >= 4 - 1e-3) and np.all(ratio <= 8 + 1e-3)


def test_softmax_loss_and_grad():
    net = make_net("layer[+1:f] = fullc:fc\n  nhidden = 4\nlayer[+0] = softmax")
    x = np.random.RandomState(12).randn(6, 1, 1, 16).astype(np.float32)
    label = jnp.asarray(np.random.RandomState(13).randint(0, 4, (6, 1)),
                        jnp.float32)
    params, state = net.init(jax.random.PRNGKey(0))

    def loss_fn(p):
        return net.apply(p, state, jnp.asarray(x), label=label, train=True,
                         rng=jax.random.PRNGKey(0)).loss

    loss, grads = jax.value_and_grad(loss_fn)(params)
    # numpy CE
    w, b = np.asarray(params["fc"]["wmat"]), np.asarray(params["fc"]["bias"])
    logits = x.reshape(6, 16) @ w + b
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    y = np.asarray(label)[:, 0].astype(int)
    ce = -np.mean(np.log(p[np.arange(6), y]))
    np.testing.assert_allclose(float(loss), ce, rtol=1e-4)
    # grad wrt logits = (p - onehot)/batch -> grad bias = col sums
    gb = (p - np.eye(4)[y]).sum(0) / 6
    np.testing.assert_allclose(np.asarray(grads["fc"]["bias"]), gb, rtol=1e-4,
                               atol=1e-6)


def test_lp_loss():
    net = make_net("layer[+1:f] = fullc:fc\n  nhidden = 3\nlayer[+0] = l2_loss")
    x = np.random.RandomState(14).randn(4, 1, 1, 16).astype(np.float32)
    label = jnp.asarray(np.random.RandomState(15).randn(4, 3), jnp.float32)
    params, state = net.init(jax.random.PRNGKey(0))
    res = net.apply(params, state, jnp.asarray(x),
                    label=jnp.zeros((4, 3)), train=True)
    # need label_vec for width-3 labels; use direct loss check instead
    w, b = np.asarray(params["fc"]["wmat"]), np.asarray(params["fc"]["bias"])
    pred = x.reshape(4, 16) @ w + b
    expect = np.mean(np.sum(pred ** 2, axis=1))
    np.testing.assert_allclose(float(res.loss), expect, rtol=1e-4)


def test_pairtest_layer():
    net = make_net("layer[+1] = pairtest-relu-relu")
    x = np.random.RandomState(16).randn(2, 1, 1, 16).astype(np.float32)
    _, res = run(net, x)
    name = net.graph.layers[0].name
    assert float(res.state[name]["diff"]) == 0.0


def test_shared_layer_params():
    net = make_net("""layer[+1:h1] = fullc:fc1
  nhidden = 16
layer[+1:h2] = share[fc1]""")
    params, _ = net.init(jax.random.PRNGKey(0))
    assert list(params.keys()) == ["fc1"]
    x = np.random.RandomState(17).randn(2, 1, 1, 16).astype(np.float32)
    _, res = run(net, x)
    w = np.asarray(params["fc1"]["wmat"])
    b = np.asarray(params["fc1"]["bias"])
    h1 = x.reshape(2, 16) @ w + b
    h2 = h1 @ w + b
    np.testing.assert_allclose(np.asarray(res.out).reshape(2, 16), h2,
                               rtol=1e-4)


@pytest.mark.parametrize("cin,hw,k,s,p", [
    (3, 23, 11, 4, 0),   # AlexNet-stem geometry (shrunk spatially)
    (3, 24, 7, 2, 3),    # ResNet-stem geometry
    (1, 13, 5, 3, 2),    # uneven: kernel not a stride multiple, odd input
    (4, 16, 4, 2, 1),    # kernel == 2*stride exactly
    (3, 10, 3, 2, 0),    # floor mode drops tail rows
])
def test_conv_space_to_depth_matches_direct(cin, hw, k, s, p):
    """The stem-conv space-to-depth lowering is an exact rewrite: compare
    against the direct conv path (forward AND input gradient)."""
    from cxxnet_tpu.layers.conv import ConvolutionLayer
    body = (f"layer[0->1] = conv:cv\n  kernel_size = {k}\n  stride = {s}\n"
            f"  pad = {p}\n  nchannel = 8")
    net = make_net(body, input_shape=f"{cin},{hw},{hw}")
    x = np.random.RandomState(11).randn(2, hw, hw, cin).astype(np.float32)
    params, state = net.init(jax.random.PRNGKey(1))
    cv = next(l for l in net.layers if getattr(l, "name", "") == "cv")
    assert cv._use_space_to_depth()

    def fwd(p, force_direct):
        if force_direct:
            orig = ConvolutionLayer._use_space_to_depth
            ConvolutionLayer._use_space_to_depth = lambda self: False
            try:
                r = net.apply(p, state, jnp.asarray(x))
            finally:
                ConvolutionLayer._use_space_to_depth = orig
        else:
            r = net.apply(p, state, jnp.asarray(x))
        return r.out

    y_s2d = np.asarray(fwd(params, False))
    y_dir = np.asarray(fwd(params, True))
    assert y_s2d.shape == y_dir.shape
    np.testing.assert_allclose(y_s2d, y_dir, rtol=1e-4, atol=1e-5)

    g_s2d = jax.grad(lambda p: jnp.sum(jnp.square(fwd(p, False))))(params)
    g_dir = jax.grad(lambda p: jnp.sum(jnp.square(fwd(p, True))))(params)
    for tag in ("wmat", "bias"):
        np.testing.assert_allclose(np.asarray(g_s2d["cv"][tag]),
                                   np.asarray(g_dir["cv"][tag]),
                                   rtol=1e-3, atol=1e-4)


def test_insanity_eval_slope_finite_when_fully_annealed():
    """The eval divisor (ub-lb)/(log ub - log lb) is 0/0 once annealing
    reaches lb == ub (the reference's formula has the same hazard,
    insanity_layer-inl.hpp:71); the guard must produce the analytic
    limit — xelu with the midpoint slope — not NaN."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.graph import build_graph
    from cxxnet_tpu.layers import create_layer
    from cxxnet_tpu.layers.base import ApplyCtx
    cfg = parse_config_string("""
netconfig=start
layer[+1:a] = insanity:ins
  lb = 4
  ub = 8
  calm_start = 0
  calm_end = 4
netconfig=end
input_shape = 1,1,8
batch_size = 2
""")
    g = build_graph(cfg)
    layer = create_layer(g.layers[0], g.defcfg)
    layer.infer_shapes([(1, 1, 8)])
    x = jnp.asarray(np.linspace(-2, 2, 16).reshape(2, 1, 1, 8),
                    jnp.float32)
    # state past calm_end: lb == ub == 6 exactly
    (out,), _ = layer.apply({}, {"step": jnp.int32(10)}, [x],
                            ApplyCtx(train=False,
                                     rng=jax.random.PRNGKey(0)))
    assert np.all(np.isfinite(np.asarray(out)))
    expect = np.where(np.asarray(x) > 0, np.asarray(x),
                      np.asarray(x) / 6.0)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5)
