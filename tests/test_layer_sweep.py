"""Sweep every registered layer type through a minimal build + forward +
gradient, so rarely-used types (fixconn, insanity_max_pooling, softplus,
bias, multi_logistic, ...) can't silently rot. The per-layer numerics are
covered by test_layers.py; this guards existence and differentiability."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.config import parse_config_string
from cxxnet_tpu.graph import KNOWN_LAYER_TYPES, build_graph
from cxxnet_tpu.model import Network

IMG = "3,16,16"     # conv-style input
FLAT = "1,1,24"     # flat input
SEQ_V = 8

# minimal per-type config snippets: (input_shape, layer lines)
CASES = {
    "fullc": (FLAT, "layer[+1] = fullc\n  nhidden = 6\n"),
    "bias": (FLAT, "layer[+0] = bias\n"),
    "relu": (FLAT, "layer[+1] = relu\n"),
    "sigmoid": (FLAT, "layer[+1] = sigmoid\n"),
    "tanh": (FLAT, "layer[+1] = tanh\n"),
    "softplus": (FLAT, "layer[+1] = softplus\n"),
    "flatten": (IMG, "layer[+1] = flatten\n"),
    "dropout": (FLAT, "layer[+0] = dropout\n  threshold = 0.3\n"),
    "conv": (IMG, "layer[+1] = conv\n  kernel_size = 3\n  nchannel = 4\n"),
    "max_pooling": (IMG, "layer[+1] = max_pooling\n  kernel_size = 2\n"),
    "avg_pooling": (IMG, "layer[+1] = avg_pooling\n  kernel_size = 2\n"),
    "sum_pooling": (IMG, "layer[+1] = sum_pooling\n  kernel_size = 2\n"),
    "relu_max_pooling": (IMG,
                         "layer[+1] = relu_max_pooling\n  kernel_size = 2\n"),
    "insanity_max_pooling": (
        IMG, "layer[+1] = insanity_max_pooling\n  kernel_size = 2\n"),
    "lrn": (IMG, "layer[+1] = lrn\n  local_size = 3\n"),
    "maxout": (IMG, "layer[+1] = conv\n  kernel_size = 3\n"
               "  nchannel = 4\nlayer[+1] = maxout\n  num_piece = 2\n"),
    "xelu": (FLAT, "layer[+1] = xelu\n  b = 2\n"),
    "insanity": (FLAT, "layer[+1] = insanity\n"),
    "rrelu": (FLAT, "layer[+1] = rrelu\n"),
    "prelu": (IMG, "layer[+1] = prelu\n"),
    "batch_norm": (IMG, "layer[+1] = batch_norm\n"),
    "batch_norm_no_ma": (IMG, "layer[+1] = batch_norm_no_ma\n"),
    "split": (FLAT, "layer[0->1,2] = split\nlayer[1,2->3] = concat\n"),
    "concat": (FLAT, "layer[0->1,2] = split\nlayer[1,2->3] = concat\n"),
    "ch_concat": (IMG, "layer[0->1,2] = split\nlayer[1,2->3] = ch_concat\n"),
    "softmax": (FLAT, "layer[+1] = fullc\n  nhidden = 4\nlayer[+0] = softmax\n"),
    "lp_loss": (FLAT, "layer[+1] = fullc\n  nhidden = 1\nlayer[+0] = lp_loss\n"),
    "l2_loss": (FLAT, "layer[+1] = fullc\n  nhidden = 1\nlayer[+0] = l2_loss\n"),
    "multi_logistic": (
        FLAT, "layer[+1] = fullc\n  nhidden = 1\nlayer[+0] = multi_logistic\n"),
    "embed": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
              f"  vocab_size = {SEQ_V}\n"),
    "posembed": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
                 f"  vocab_size = {SEQ_V}\nlayer[+1] = posembed\n"),
    "layernorm": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
                  f"  vocab_size = {SEQ_V}\nlayer[+1] = layernorm\n"),
    "mha": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
            f"  vocab_size = {SEQ_V}\nlayer[+1] = mha\n  nhead = 2\n"),
    "ffn": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
            f"  vocab_size = {SEQ_V}\nlayer[+1] = ffn\n  nhidden = 16\n"),
    "moe": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
            f"  vocab_size = {SEQ_V}\nlayer[+1] = moe\n  num_expert = 2\n"),
    "seqfc": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
              f"  vocab_size = {SEQ_V}\nlayer[+1] = seqfc\n  nhidden = 5\n"),
    "add": (f"1,1,12", f"layer[+1:e] = embed\n  nhidden = 8\n"
            f"  vocab_size = {SEQ_V}\nlayer[+1:f] = layernorm\n"
            f"layer[e,f->s] = add\n"),
    "lmloss": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
               f"  vocab_size = {SEQ_V}\nlayer[+1] = seqfc\n"
               f"  nhidden = {SEQ_V}\nlayer[+0] = lmloss\n"),
    "rmsnorm": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
                f"  vocab_size = {SEQ_V}\nlayer[+1] = rmsnorm\n"),
    "mla": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
            f"  vocab_size = {SEQ_V}\nlayer[+1] = mla\n  nhead = 2\n"
            f"  q_lora_rank = 6\n  kv_lora_rank = 4\n"
            f"  qk_nope_head_dim = 4\n  qk_rope_head_dim = 2\n"
            f"  v_head_dim = 4\n"),
    "gqa": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
            f"  vocab_size = {SEQ_V}\nlayer[+1] = gqa\n  nhead = 4\n"
            "  nkvhead = 2\n  head_dim = 6\n  window = 5\n"
            "  head_gate = 1\n  rotary_dim = 4\n  rope_type = yarn\n"
            "  rope_factor = 4\n  rope_original_max_position = 8\n"),
    "dsa": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
            f"  vocab_size = {SEQ_V}\nlayer[+1] = dsa\n  nhead = 4\n"
            "  nkvhead = 2\n  head_dim = 6\n  qk_norm = 1\n"
            "  mrope_section = 1,1,1\n  index_heads = 2\n"
            "  index_head_dim = 4\n  index_topk = 5\n"),
    "shortconv": (f"1,1,12", f"layer[+1] = embed\n  nhidden = 8\n"
                  f"  vocab_size = {SEQ_V}\nlayer[+1] = shortconv\n"
                  "  conv_L_cache = 3\n"),
    "label_ids": (f"1,1,12", f"layer[+1] = label_ids\nlayer[+1] = embed\n"
                  f"  nhidden = 8\n  vocab_size = {SEQ_V}\n"),
}

# covered separately: share/pairtest/fixconn in test_layers.py and below,
# plugin needs a user class file
# (exercised by tests/test_layers.py::test_plugin_layer).
UNTESTABLE = {"share", "pairtest", "fixconn", "plugin"}


def test_sweep_covers_every_registered_type():
    assert KNOWN_LAYER_TYPES - set(CASES) - UNTESTABLE == set()


@pytest.mark.parametrize("ltype", sorted(CASES))
def test_layer_forward_and_grad(ltype):
    shape, lines = CASES[ltype]
    cfg_text = (f"netconfig=start\n{lines}netconfig=end\n"
                f"input_shape = {shape}\nbatch_size = 4\n")
    cfg = parse_config_string(cfg_text)
    net = Network(build_graph(cfg), cfg)
    params, state = net.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    c, y, x = (int(v) for v in shape.split(","))
    if ltype in ("embed", "posembed", "layernorm", "mha", "ffn", "moe",
                 "seqfc", "add", "lmloss", "rmsnorm", "mla", "gqa", "dsa",
                 "shortconv", "label_ids"):
        data = jnp.asarray(rng.randint(0, SEQ_V, (4, 1, 1, x))
                           .astype(np.float32))
    elif c == 1 and y == 1:
        data = jnp.asarray(rng.randn(4, 1, 1, x).astype(np.float32))
    else:
        data = jnp.asarray(rng.randn(4, y, x, c).astype(np.float32))

    res = net.apply(params, state, data, train=True,
                    rng=jax.random.PRNGKey(1))
    assert np.all(np.isfinite(np.asarray(res.out)))

    if params:   # differentiate an arbitrary scalar through the layer
        def f(p):
            r = net.apply(p, state, data, train=True,
                          rng=jax.random.PRNGKey(1))
            return jnp.sum(r.out.astype(jnp.float32) ** 2)
        g = jax.grad(f)(params)
        for leaf in jax.tree_util.tree_leaves(g):
            assert np.all(np.isfinite(np.asarray(leaf)))


def test_fixconn(tmp_path):
    wf = tmp_path / "w.txt"
    w = np.eye(24, 6, dtype=np.float32)
    wf.write_text("24 6 " + " ".join(str(v) for v in w.ravel()))
    cfg_text = (f"netconfig=start\nlayer[+1] = fixconn\n"
                f"  weight_file = {wf}\nnetconfig=end\n"
                f"input_shape = {FLAT}\nbatch_size = 4\n")
    cfg = parse_config_string(cfg_text)
    net = Network(build_graph(cfg), cfg)
    params, state = net.init(jax.random.PRNGKey(0))
    data = jnp.asarray(np.random.RandomState(0)
                       .randn(4, 1, 1, 24).astype(np.float32))
    out = net.apply(params, state, data, train=False).out
    np.testing.assert_allclose(np.asarray(out).reshape(4, 6),
                               np.asarray(data).reshape(4, 24) @ w, atol=1e-6)


def test_maxout_values_and_shapes():
    """maxout (the reference declares kMaxout, layer.h:344, but ships no
    implementation — this one is real): channels group by num_piece and
    take the elementwise max; works on conv AND flat nodes."""
    import jax
    from cxxnet_tpu.layers import create_layer
    from cxxnet_tpu.layers.base import ApplyCtx
    rng = np.random.RandomState(3)
    # conv node: (b, h, w, c=6), num_piece=3 -> c_out=2
    cfg = parse_config_string(
        "netconfig=start\nlayer[+1] = maxout\n  num_piece = 3\n"
        "netconfig=end\ninput_shape = 6,4,4\nbatch_size = 2\n")
    g = build_graph(cfg)
    layer = create_layer(g.layers[0], g.defcfg)
    assert layer.infer_shapes([(6, 4, 4)]) == [(2, 4, 4)]
    x = rng.randn(2, 4, 4, 6).astype(np.float32)
    (out,), _ = layer.apply({}, {}, [jnp.asarray(x)],
                            ApplyCtx(train=True,
                                     rng=jax.random.PRNGKey(0)))
    np.testing.assert_allclose(np.asarray(out),
                               x.reshape(2, 4, 4, 2, 3).max(-1),
                               rtol=1e-6)
    # flat node: features on the trailing axis
    cfg = parse_config_string(
        "netconfig=start\nlayer[+1] = maxout\n  num_piece = 2\n"
        "netconfig=end\ninput_shape = 1,1,8\nbatch_size = 4\n")
    g = build_graph(cfg)
    layer = create_layer(g.layers[0], g.defcfg)
    assert layer.infer_shapes([(1, 1, 8)]) == [(1, 1, 4)]
    xf = rng.randn(4, 1, 1, 8).astype(np.float32)
    (outf,), _ = layer.apply({}, {}, [jnp.asarray(xf)],
                             ApplyCtx(train=True,
                                      rng=jax.random.PRNGKey(0)))
    np.testing.assert_allclose(np.asarray(outf),
                               xf.reshape(4, 1, 1, 4, 2).max(-1),
                               rtol=1e-6)
    # indivisible count: clean error
    layer2 = create_layer(g.layers[0], g.defcfg)
    layer2.num_piece = 3
    with pytest.raises(ValueError, match="num_piece"):
        layer2.infer_shapes([(1, 1, 8)])
