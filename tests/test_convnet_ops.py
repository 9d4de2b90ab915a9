"""One implementation per convnet op: each layer's jnp form, forward
and gradient, against the independent float32 reference of
``benchmarks/reference.py`` (read, never edited, here), in float32 and
bfloat16; the pooling layers' tie and zero-gradient rules; the in-step
decode-normalize and the int8 ops against numpy; three training steps
against a reference loop; and the pins of PR 30's deletion (no Pallas
kernel outside ``ops/attention.py``, ``fused_kernels`` and
``CXXNET_FUSED_KERNELS`` read by nothing)."""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.config import parse_config_string
from cxxnet_tpu.graph import build_graph
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.model import Network
from cxxnet_tpu.ops.quant import int8_conv, int8_matmul, quantize_act
from cxxnet_tpu.ops.stem import decode_normalize
from cxxnet_tpu.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reference  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
DTYPES = [pytest.param(F32, id="float32"), pytest.param(BF16, id="bfloat16")]


def tol(dtype, f32, bf16):
    return f32 if dtype == F32 else bf16


def close(a, b, rtol, atol=None):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=rtol, atol=rtol if atol is None else atol)


def _graph(body, input_shape, dtype=F32):
    c, h, w = input_shape
    return build_graph(parse_config_string(
        f"netconfig=start\n{body}\nnetconfig=end\n"
        f"input_shape = {c},{h},{w}\n"
        f"compute_dtype = {jnp.dtype(dtype).name}\n"))


def _pair(body, input_shape, dtype, ref_body=None, ref_scale=1.0,
          train=True):
    """``(program, reference, params)``: the layers of ``body`` as the
    program runs them (``Network.apply``, compute dtype ``dtype``) and
    as ``benchmarks/reference.py`` does in float32, both as
    ``f(params, x) -> output`` of the last layer; ``ref_body`` where the
    reference spells the same arithmetic with its own layer kinds."""
    g = _graph(body, input_shape, dtype)
    net = Network(g, g.defcfg)
    params, state = net.init(jax.random.PRNGKey(0))
    # off their initial values: a batch_norm's slope of 1 and bias of 0
    # would hide a swapped or dropped parameter
    leaves, treedef = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(treedef, [
        p + 0.3 * jax.random.normal(jax.random.PRNGKey(10 + i), p.shape)
        for i, p in enumerate(leaves)])
    rg = g if ref_body is None else _graph(ref_body, input_shape)
    top = rg.layers[-1].nindex_out[0]

    def program(p, x):
        return net.apply(p, state, x, train=train).out

    def ref(p, x):
        return ref_scale * reference.forward(
            rg.layers, rg.defcfg, p, state, x, train)[top]
    return program, ref, params


def _input(shape, dtype, seed=0, scale=2.0, shift=1.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape) * scale + shift
    return x.astype(dtype)


def _check(body, shape, dtype, fwd_tol, grad_tol=None, **kw):
    """Forward, and with ``grad_tol`` the gradient of a weighted sum of
    the output with respect to parameters and input, program against
    reference on the same (``dtype``-rounded) input."""
    b, h, w, c = shape
    program, ref, params = _pair(body, (c, h, w), dtype, **kw)
    x = _input(shape, dtype)
    y, want = program(params, x), ref(params, x)
    assert y.dtype == jnp.dtype(dtype) and y.shape == want.shape
    close(y, want, fwd_tol)
    if grad_tol is None:
        return
    ct = jnp.cos(jnp.arange(want.size, dtype=F32).reshape(want.shape) * 0.1)
    loss = lambda fn: lambda p, x: jnp.sum(fn(p, x).astype(F32) * ct)
    got = jax.grad(loss(program), (0, 1))(params, x)
    exp = jax.grad(loss(ref), (0, 1))(params, x.astype(F32))
    for a, e in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(exp)):
        assert a.shape == e.shape
        # against the tensor's own scale: a relu mask that flips under
        # bfloat16 rounding moves single elements by a whole term
        close(a, e, grad_tol,
              atol=grad_tol * max(1.0, float(jnp.max(jnp.abs(e)))))


# -- batch norm (+ the relu the graph folds into it) --------------------------

def _bn(act, two_pass=False):
    return ("layer[0->1] = batch_norm:bn\n"
            + ("  bn_two_pass = 1\n" if two_pass else "")
            + ("layer[1->2] = relu:ac\n" if act == "relu" else ""))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("act", ["none", "relu"])
@pytest.mark.parametrize("two_pass", [False, True])
def test_batch_norm_forward(dtype, act, two_pass):
    _check(_bn(act, two_pass), (8, 4, 4, 24), dtype, tol(dtype, 1e-5, 3e-2))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("act", ["none", "relu"])
def test_batch_norm_grad(dtype, act):
    _check(_bn(act), (8, 4, 4, 16), dtype, tol(dtype, 1e-5, 3e-2),
           grad_tol=tol(dtype, 2e-4, 1e-1))


def test_bn_two_pass_knob():
    """bn_two_pass = 1 (ADVICE r5) reaches the layer and changes
    nothing for well-conditioned inputs."""
    b = _batch()
    vals = []
    for extra in ("", "bn_two_pass = 1\n"):
        tr = _trainer(extra)
        assert tr.net.layers[1].two_pass is bool(extra)
        tr.update(b)
        vals.append(tr.last_loss)
    assert abs(vals[0] - vals[1]) < 2e-3


# -- conv + bias + relu (the epilogue XLA fuses by itself) --------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("act,has_bias", [("relu", True), ("relu", False),
                                          ("none", True)])
def test_conv_bias_act(dtype, act, has_bias):
    """The bfloat16 ``none``-with-bias case is the one the kernel suite's
    twin failed: it summed dbias in bfloat16, this reference in float32."""
    body = ("layer[0->1] = conv:cv\n  kernel_size = 3\n  pad = 1\n"
            "  nchannel = 24\n"
            + ("" if has_bias else "  no_bias = 1\n")
            + ("layer[1->2] = relu:ac\n" if act == "relu" else ""))
    _check(body, (8, 4, 4, 8), dtype, tol(dtype, 1e-5, 3e-2),
           grad_tol=tol(dtype, 1e-4, 5e-2))


# -- lrn ----------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("nsize", [3, 5, 4])
def test_lrn(dtype, nsize):
    body = (f"layer[0->1] = lrn:lrn\n  local_size = {nsize}\n"
            "  alpha = 0.001\n  beta = 0.75\n  knorm = 1")
    _check(body, (8, 4, 4, 24), dtype, tol(dtype, 1e-5, 2e-2),
           grad_tol=tol(dtype, 5e-4, 5e-2))


# -- pooling ------------------------------------------------------------------

def _pool(kind, k, s, pad=0):
    return (f"layer[0->1] = {kind}:p\n  kernel_size = {k}\n  stride = {s}\n"
            f"  pad = {pad}")


_RELU_THEN_MAX = "layer[0->1] = relu:r\n" + _pool("max_pooling", 2, 2) \
    .replace("[0->1]", "[1->2]")

POOL_CASES = [
    # (shape, program's layers, reference's layers, reference's scale)
    ((8, 8, 8, 16), _pool("max_pooling", 2, 2), None, 1.0),
    ((8, 8, 8, 16), _pool("avg_pooling", 2, 2), None, 1.0),
    ((8, 8, 8, 16), _pool("sum_pooling", 2, 2),
     _pool("avg_pooling", 2, 2), 4.0),
    ((8, 8, 8, 16), _pool("relu_max_pooling", 2, 2), _RELU_THEN_MAX, 1.0),
    ((8, 7, 7, 16), _pool("avg_pooling", 7, 1), None, 1.0),   # IBN head
    ((8, 4, 4, 16), _pool("max_pooling", 4, 4), None, 1.0),   # global max
]
POOL_IDS = ["max", "avg", "sum", "relu_max", "global_avg", "global_max"]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", POOL_CASES, ids=POOL_IDS)
def test_pooling(case, dtype):
    shape, body, ref_body, ref_scale = case
    _check(body, shape, dtype, tol(dtype, 1e-6, 2e-2),
           grad_tol=tol(dtype, 1e-6, 2e-2), ref_body=ref_body,
           ref_scale=ref_scale)


@pytest.mark.parametrize("k,s,pad,size", [(3, 2, 0, 8), (3, 2, 1, 7),
                                          (2, 2, 0, 7)],
                         ids=["overlap_ceil", "padded", "ragged_edge"])
def test_pooling_ceil_mode_geometry(k, s, pad, size):
    """cxxnet's ceil-mode windows: overlapping, padded, and hanging over
    the edge (the twelve 3x3/2 poolings of the flagship are the first)."""
    for kind in ("max_pooling", "avg_pooling"):
        _check(_pool(kind, k, s, pad), (4, size, size, 8), F32, 1e-6,
               grad_tol=1e-6)


@pytest.mark.parametrize("dtype", DTYPES)
def test_max_pooling_tie_goes_to_the_first_cell(dtype):
    """All-equal windows: select-and-scatter routes the cotangent to the
    FIRST cell of each window (row-major), nowhere else."""
    program, _, params = _pair(_pool("max_pooling", 2, 2), (8, 4, 4), dtype)
    x = jnp.ones((8, 4, 4, 8), dtype)
    g = jax.grad(lambda x: jnp.sum(program(params, x).astype(F32)))(x)
    want = np.zeros((8, 4, 4, 8), np.float32)
    want[:, ::2, ::2, :] = 1.0
    np.testing.assert_array_equal(np.asarray(g, np.float32), want)


@pytest.mark.parametrize("dtype", DTYPES)
def test_relu_max_pooling_zero_window_has_zero_gradient(dtype):
    """relu's gradient at zero is zero: an all-zero window gives no dx."""
    program, _, params = _pair(_pool("relu_max_pooling", 2, 2), (8, 4, 4),
                               dtype)
    x = jnp.zeros((8, 4, 4, 8), dtype)
    g = jax.grad(lambda x: jnp.sum(program(params, x).astype(F32)))(x)
    assert not np.any(np.asarray(g, np.float32))


# -- the in-step decode-normalize (input_fold) --------------------------------

@pytest.mark.parametrize("mean_kind", ["none", "channel", "image"])
@pytest.mark.parametrize("out_dtype", ["float32", "bfloat16"])
def test_decode_normalize_against_numpy(mean_kind, out_dtype):
    rng = np.random.RandomState(7)
    x = rng.randint(0, 256, (8, 8, 16, 3)).astype(np.uint8)
    mean = {"none": None,
            "channel": np.asarray([120.0, 110.0, 100.0], np.float32),
            "image": rng.rand(8, 16, 3).astype(np.float32) * 255}[mean_kind]
    factor = np.float32(1.0 / 255.0)
    y = decode_normalize(jnp.asarray(x), mean, jnp.float32(factor),
                         out_dtype)
    assert y.dtype == jnp.dtype(out_dtype)
    want = x.astype(np.float32)
    if mean is not None:
        want = want - mean
    # float32 arithmetic, ONE cast to the output dtype
    want = (want * factor).astype(jnp.dtype(out_dtype))
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  want.astype(np.float32))


# -- int8 ops against integer numpy -------------------------------------------

def _int8_want(acc, w_scale, act_scale, bias, act):
    y = acc.astype(np.float32) * (w_scale * np.float32(act_scale / 127.0))
    if bias is not None:
        y = y + bias
    return np.maximum(y, 0.0) if act == "relu" else y


@pytest.mark.parametrize("op", ["matmul", "conv"])
@pytest.mark.parametrize("act,has_bias", [("relu", True), ("relu", False),
                                          ("none", True)])
def test_int8_ops_against_integer_numpy(op, act, has_bias):
    rng = np.random.RandomState(11)
    n, act_scale = 16, 2.5
    w_scale = (rng.rand(n) * 0.02 + 0.001).astype(np.float32)
    bias = rng.randn(n).astype(np.float32) if has_bias else None
    if op == "matmul":
        x = rng.randn(8, 32).astype(np.float32) * 2
        wq = rng.randint(-127, 128, (32, n)).astype(np.int8)
        y = int8_matmul(jnp.asarray(x), jnp.asarray(wq), w_scale, act_scale,
                        bias, act)
        xq = np.asarray(quantize_act(jnp.asarray(x), act_scale), np.int64)
        acc = xq @ wq.astype(np.int64)
    else:
        x = rng.randn(2, 6, 6, 4).astype(np.float32) * 2
        wq = rng.randint(-127, 128, (3, 3, 4, n)).astype(np.int8)
        y = int8_conv(jnp.asarray(x), jnp.asarray(wq), w_scale, act_scale,
                      bias, act)
        xq = np.asarray(quantize_act(jnp.asarray(x), act_scale), np.int64)
        acc = np.zeros((2, 4, 4, n), np.int64)
        for i in range(3):
            for j in range(3):
                acc += np.einsum("bhwc,cn->bhwn", xq[:, i:i + 4, j:j + 4],
                                 wq[i, j].astype(np.int64))
    # the quantizer itself: symmetric, saturating at +-act_scale
    assert np.abs(xq).max() == 127
    np.testing.assert_array_equal(
        xq, np.round(np.clip(np.asarray(x) / np.float32(act_scale), -1, 1)
                     * 127.0))
    assert y.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(y), _int8_want(acc, w_scale, act_scale, bias, act),
        rtol=1e-6, atol=1e-6)


# -- whole train steps --------------------------------------------------------

_TAIL = """
eta = 0.05
momentum = 0.9
wd = 0.0001
dev = cpu:0-0
eval_train = 0
"""

CONV_NET = """
input_shape = 3,8,8
batch_size = 16
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 24
  pad = 1
  no_bias = 1
layer[1->2] = batch_norm:bn1
layer[2->3] = relu:r1
layer[3->4] = lrn:l1
  local_size = 5
layer[4->5] = conv:c2
  kernel_size = 3
  nchannel = 16
  pad = 1
layer[5->6] = relu:r2
layer[6->7] = flatten:f
layer[7->8] = fullc:fc1
  nhidden = 32
layer[8->9] = relu:r3
layer[9->10] = fullc:fc2
  nhidden = 4
layer[+0] = softmax
netconfig = end
"""

#: conv + batch_norm + relu (the flagship's site) behind a pool
BN_TOY = """
input_shape = 3,8,8
batch_size = 16
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 24
  pad = 1
  no_bias = 1
layer[1->2] = batch_norm:bn1
layer[2->3] = relu:r1
layer[3->4] = max_pooling:p1
  kernel_size = 2
  stride = 2
layer[4->5] = flatten:f
layer[5->6] = fullc:fc
  nhidden = 4
layer[+0] = softmax
netconfig = end
"""

#: AlexNet's kinds: conv + bias + relu, lrn, pool, fullc + bias, sgd
ALEX_TOY = """
input_shape = 3,8,8
batch_size = 16
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 24
  pad = 1
layer[1->2] = relu:r1
layer[2->3] = lrn:l1
  local_size = 5
layer[3->4] = max_pooling:p1
  kernel_size = 2
  stride = 2
layer[4->5] = flatten:f
layer[5->6] = fullc:fc1
  nhidden = 32
layer[6->7] = relu:r2
layer[7->8] = fullc:fc2
  nhidden = 4
layer[+0] = softmax
netconfig = end
"""


def _batch():
    rng = np.random.RandomState(0)
    return DataBatch(
        data=rng.rand(16, 8, 8, 3).astype(np.float32),
        label=rng.randint(0, 4, size=(16, 1)).astype(np.float32))


def _trainer(extra="", net=CONV_NET):
    tr = Trainer(parse_config_string(net + _TAIL + extra))
    tr.init_model()
    return tr


def _step_text(tr) -> str:
    return tr.lower_train_step(_batch()).as_text()


@functools.lru_cache(maxsize=None)
def _plain_step_text() -> str:
    return _step_text(_trainer())


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("net", [BN_TOY, ALEX_TOY],
                         ids=["conv_bn_relu", "alexnet_like"])
def test_default_train_step_is_xla_only(monkeypatch, backend, net):
    """The whole jitted train step (forward, backward, optimizer): no
    Pallas kernel and no host callback of any kind in its jaxpr —
    whatever the backend says it is — and nothing in the selection log:
    a convnet's ops choose no implementation."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    tr = _trainer(net=net)
    step, args = tr._train_step_call(_batch())
    jaxpr = str(jax.make_jaxpr(step)(*args))
    assert "pallas_call" not in jaxpr and "callback" not in jaxpr
    assert tr.net.fused_log == {}


def test_relu_fold_keeps_every_node_the_reference_s_value():
    """graph.act_fusion_plan folds bn->relu / conv->relu / fullc->relu
    into the producer. The net's output equals the reference's, which
    knows no fold, and so does every captured node but a folded
    producer's own, which holds the post-activation value (the
    documented capture semantics)."""
    tr = _trainer()
    net, g = tr.net, tr.graph
    assert net._act_folded and set(net._fuse_act.values()) == {"relu"}
    b = _batch()
    res = net.apply(tr.params, tr.net_state, jnp.asarray(b.data),
                    train=False, capture_nodes=True)
    want = reference.forward(g.layers, g.defcfg, tr.params, tr.net_state,
                             b.data, False)
    produced_by = {s.nindex_out[0]: li for li, s in enumerate(g.layers)}
    for ni, name in enumerate(g.node_names):
        if ni not in want or name not in res.nodes:
            continue
        exp = want[ni]
        if produced_by.get(ni) in net._fuse_act:
            exp = jnp.maximum(exp, 0.0)
        close(res.nodes[name], exp, 2e-5)
    close(res.out, want[g.layers[-1].nindex_out[0]], 2e-5)


def _reference_losses(tr0, updater, steps):
    """``steps`` losses of a plain loop: the reference's float32 loss and
    ``jax.grad`` of it, and the updater's arithmetic in numpy."""
    g = tr0.graph
    loss_fn = jax.jit(jax.value_and_grad(
        reference.make_loss_fn(g.layers, g.defcfg)))
    w = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                               tr0.mesh.gather(tr0.params))
    zeros = lambda: jax.tree_util.tree_map(np.zeros_like, w)
    m1, m2 = zeros(), zeros()
    h = tr0.optimizer.hypers["wmat"]
    lr, mu, wd, d1, d2 = h.base_lr, h.momentum, h.wd, h.beta1_decay, \
        h.beta2_decay
    b = _batch()
    losses = []
    for t in range(1, steps + 1):
        loss, grads = loss_fn(
            jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), w),
            b.data, b.label)
        losses.append(float(loss))
        for layer in w:
            for tag in w[layer]:
                p = w[layer][tag]
                gr = np.asarray(grads[layer][tag], np.float64) + wd * p
                if updater == "adam":
                    m1[layer][tag] += d1 * (gr - m1[layer][tag])
                    m2[layer][tag] += d2 * (gr * gr - m2[layer][tag])
                    lr_t = lr * np.sqrt(1 - (1 - d2) ** t) \
                        / (1 - (1 - d1) ** t)
                    w[layer][tag] = p - lr_t * m1[layer][tag] \
                        / (np.sqrt(m2[layer][tag]) + 1e-8)
                    continue
                new_m = mu * m1[layer][tag] - lr * gr
                w[layer][tag] = p + (new_m if updater == "sgd" else
                                     (1 + mu) * new_m - mu * m1[layer][tag])
                m1[layer][tag] = new_m
    return losses


@pytest.mark.parametrize("updater,extra,dtype,bound", [
    ("sgd", "", "float32", 2e-3),
    ("nag", "updater = nag\n", "float32", 2e-3),
    ("adam", "updater = adam\neta = 0.002\n", "float32", 2e-3),
    ("sgd", "", "bfloat16", 5e-2)],
    ids=["sgd", "nag", "adam", "sgd-bfloat16"])
def test_three_steps_track_a_reference_loop(updater, extra, dtype, bound):
    """Forward, backward and update of the program over three steps
    against the reference's loss, its ``jax.grad`` and a numpy update:
    every step's loss, and the loss falls."""
    tr = _trainer(extra + f"compute_dtype = {dtype}\n")
    want = _reference_losses(_trainer(extra), updater, 3)
    b = _batch()
    got = []
    for _ in range(3):
        tr.update(b)
        got.append(float(tr.last_loss))
    assert got[-1] < got[0]
    for a, e in zip(got, want):
        assert abs(a - e) < bound, (got, want)


# -- pins of the deletion (PR 30) ---------------------------------------------

def test_pallas_call_sites_live_in_attention_only():
    sites = []
    pkg = os.path.join(ROOT, "cxxnet_tpu")
    for d, _, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if re.search(r"\bpallas_call\(", fh.read()):
                        sites.append(os.path.relpath(os.path.join(d, f), pkg))
    assert sites == [os.path.join("ops", "attention.py")]


@pytest.mark.parametrize("value", ["auto", "1", "0"])
def test_fused_kernels_key_is_read_by_nothing(value):
    """A conf that still carries the key (the benchmark's toy confs do)
    builds the step a conf without it builds: the key is what every
    unknown global key of the dialect is, accepted and ignored."""
    assert _step_text(_trainer(f"fused_kernels = {value}\n")) \
        == _plain_step_text()


def test_fused_kernels_env_is_read_by_nothing(monkeypatch):
    plain = _plain_step_text()
    monkeypatch.setenv("CXXNET_FUSED_KERNELS", "1")
    assert _step_text(_trainer()) == plain


def test_a_convnet_s_selection_log_is_empty():
    tr = _trainer()
    tr.update(_batch())
    assert tr.net.fused_log == {}


def test_ops_fused_keeps_what_the_benchmark_imports():
    """``benchmarks/run.py`` may not change with the program: the module
    path, the names it imports from it and ``Network.fused_log`` stay."""
    import cxxnet_tpu.ops.fused as sel
    with open(os.path.join(ROOT, "benchmarks", "run.py")) as f:
        src = f.read()
    names = re.findall(r"from cxxnet_tpu\.ops\.fused import ([\w, ]+)", src)
    assert names, "benchmarks/run.py no longer imports the selection log"
    for name in ",".join(names).replace(" ", "").split(","):
        assert callable(getattr(sel, name)), name
    assert "net.fused_log" in src
    for name in ("selection_site", "selection_summary", "note_attention",
                 "note_grouped", "SelectionLog"):
        assert hasattr(sel, name), name
