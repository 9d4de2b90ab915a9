"""Convnet ops on a mesh: GSPMD partitions the one implementation.

With the batch sharded over ``data`` on the faked CPU devices, batch
norm's moments are the GLOBAL batch's (sync-BN is the partitioner's
all-reduce, bit-exact on exactly-summable data), gradients and a
bias's cross-shard sum match one device, a data-parallel trainer tracks
the single-device one, and pipeline / sequence meshes build and train
with nothing to gate and nothing to warn about.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from cxxnet_tpu.config import parse_config_string
from cxxnet_tpu.graph import build_graph
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.model import Network
from cxxnet_tpu.parallel import make_mesh_context
from cxxnet_tpu.telemetry.registry import get_registry
from cxxnet_tpu.trainer import Trainer

pytestmark = pytest.mark.quick


def _mesh_ctx(n=8, mp=1):
    return make_mesh_context(devices=jax.devices()[:n], model_parallel=mp)


def _int_batch(shape, lo=0, hi=64, scale=0.125, seed=0):
    """f32 data whose values (and squares) sum EXACTLY in f32: bitwise
    moment parity then holds regardless of reduction association."""
    r = np.random.RandomState(seed)
    return (r.randint(lo, hi, shape) * scale).astype(np.float32)


def _layers(body, input_shape):
    c, h, w = input_shape
    g = build_graph(parse_config_string(
        f"netconfig=start\n{body}\nnetconfig=end\n"
        f"input_shape = {c},{h},{w}\n"))
    net = Network(g, g.defcfg)
    params, state = net.init(jax.random.PRNGKey(0))
    return net, params, state


_BN_RELU = "layer[0->1] = batch_norm:bn\nlayer[1->2] = relu:ac"


def test_sync_bn_moments_are_the_global_batch_s_bit_for_bit():
    ctx = _mesh_ctx()
    net, params, state = _layers(_BN_RELU, (8, 4, 8))
    params["bn"] = {"wmat": jnp.asarray(np.linspace(0.5, 1.5, 8), jnp.float32),
                    "bias": jnp.asarray(np.linspace(-0.2, 0.3, 8),
                                        jnp.float32)}
    x = jnp.asarray(_int_batch((16, 4, 8, 8)))
    xs = jax.device_put(x, NamedSharding(ctx.mesh, P("data")))
    def fwd(x):
        res = net.apply(params, state, x, train=True)
        return res.out, res.state["bn"]
    (y_m, st_m), (y_1, st_1) = jax.jit(fwd)(xs), jax.jit(fwd)(x)
    assert len(y_m.sharding.device_set) == 8
    # exact sums -> any association gives identical bits, so a
    # shard-local-moment bug cannot hide inside a tolerance
    for k in ("running_exp", "running_var"):
        assert np.array_equal(np.asarray(st_m[k]), np.asarray(st_1[k])), k
    mean = np.asarray(x).mean(axis=(0, 1, 2))
    np.testing.assert_array_equal(
        np.asarray(st_m["running_exp"]),
        (mean * np.float32(1 - net.layers[0].bn_momentum)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(y_m), np.asarray(y_1),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("body,input_shape,shape", [
    (_BN_RELU, (8, 4, 8), (16, 4, 8, 8)),
    ("layer[0->1] = conv:cv\n  kernel_size = 3\n  pad = 1\n  nchannel = 8\n"
     "layer[1->2] = relu:ac", (8, 2, 4), (8, 2, 4, 8))],
    ids=["sync_bn", "conv_dbias"])
def test_grads_on_the_dp_mesh_match_one_device(body, input_shape, shape):
    """The cross-shard sums autodiff needs (batch norm's, a bias's) are
    the partitioner's: parameters replicated, batch sharded."""
    ctx = _mesh_ctx()
    net, params, state = _layers(body, input_shape)
    x = jnp.asarray(_int_batch(shape, lo=-32, hi=32))
    xs = jax.device_put(x, NamedSharding(ctx.mesh, P("data")))

    def loss(p, x):
        y = net.apply(p, state, x, train=True).out
        return jnp.sum(y * jnp.cos(y))
    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
    for a, b in zip(jax.tree_util.tree_leaves(grad(params, xs)),
                    jax.tree_util.tree_leaves(grad(params, x))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


CONV_CFG = """
netconfig=start
layer[0->1] = conv:c1
  kernel_size = 3
  pad = 1
  nchannel = 8
layer[1->2] = batch_norm:bn1
layer[2->3] = relu:r1
layer[3->4] = max_pooling:mp1
  kernel_size = 2
  stride = 2
layer[4->5] = flatten:fl
layer[5->6] = fullc:fc
  nhidden = 4
  init_sigma = 0.01
layer[6->6] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 8
eta = 0.05
eval_train = 0
"""


def _batch(seed=0):
    r = np.random.RandomState(seed)
    return DataBatch(
        data=(r.randint(0, 16, (8, 8, 8, 3)) * 0.25).astype(np.float32),
        label=r.randint(0, 4, (8, 1)).astype(np.float32))


def _run(tr, steps=5, seed=0):
    losses = []
    for _ in range(steps):
        tr.update(_batch(seed))
        losses.append(float(tr.last_loss))
    return losses


@pytest.mark.parametrize("dtype,bound", [("float32", 5e-3),
                                         ("bfloat16", 5e-2)])
@pytest.mark.parametrize("mp", [1, 2], ids=["dp8", "dp4xtp2"])
def test_trainer_on_a_mesh_tracks_the_single_device_run(dtype, bound, mp):
    cfg = parse_config_string(CONV_CFG + f"compute_dtype = {dtype}\n")
    tr_m = Trainer(cfg, mesh_ctx=_mesh_ctx(mp=mp))
    tr_m.init_model()
    tr_1 = Trainer(cfg, mesh_ctx=_mesh_ctx(n=1))
    tr_1.init_model()
    lm, l1 = _run(tr_m), _run(tr_1)
    assert l1[-1] < l1[0]
    for a, b in zip(lm, l1):
        assert abs(a - b) < bound, (lm, l1)


def _nothing_gated(capsys):
    said = capsys.readouterr().out
    assert "fused" not in said and "reference path" not in said, said
    assert get_registry().get("cxxnet_fused_fallback_total") is None


@pytest.mark.parametrize("knob", ["fused_kernels = 1\n", ""],
                         ids=["stale_key", "plain"])
def test_pp_mesh_builds_and_trains_with_nothing_to_gate(capsys, knob):
    """A pipeline mesh is one more place the one implementation runs:
    no gate, no warning, no fallback counter — with or without the key
    an old conf may still carry."""
    cfg = parse_config_string(
        CONV_CFG.replace("layer[5->6] = fullc:fc",
                         "layer[5->6] = fullc:fc\n  stage = 1")
        + knob + "pipeline_parallel = 2\n")
    tr = Trainer(cfg, mesh_ctx=make_mesh_context(
        devices=jax.devices()[:2], pipeline_parallel=2))
    tr.init_model()
    losses = _run(tr, steps=3)
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    _nothing_gated(capsys)


def test_sp_meshes_build_and_train_with_nothing_to_gate(capsys):
    lm_cfg = parse_config_string("""
netconfig=start
layer[+1:e0] = embed:tok_embed
  nhidden = 16
  vocab_size = 8
layer[+1:n1] = layernorm:ln1
layer[+1:f1] = ffn:ffn1
  nhidden = 32
layer[+1:lg] = seqfc:lm_head
  nhidden = 8
layer[+0] = lmloss
netconfig=end
input_shape = 1,1,16
label_vec[0,16) = label
batch_size = 8
eval_train = 0
""")
    r = np.random.RandomState(0)
    b = DataBatch(data=r.randint(0, 8, (8, 1, 1, 16)).astype(np.float32),
                  label=r.randint(0, 8, (8, 16)).astype(np.float32))
    for n, mp in ((2, 1), (4, 2)):          # sp, and sp x tp
        tr = Trainer(lm_cfg, mesh_ctx=make_mesh_context(
            devices=jax.devices()[:n], seq_parallel=2, model_parallel=mp))
        tr.init_model()
        tr.update(b)
        assert np.isfinite(float(tr.last_loss))
    _nothing_gated(capsys)
