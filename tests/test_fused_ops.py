"""Fused Pallas kernel suite (ops/fused_*): forward + gradient parity
against the jnp references in fp32 and bf16 under ``interpret=True`` on
CPU, kernel-selection probes (the fused op must actually be in the
jaxpr when selected, and ``fused_kernels = 0`` / the env kill switch
must restore the reference), and fused-vs-reference training parity
end-to-end through the Trainer."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.config import ConfigError, parse_config_string
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.ops.fused import kernels_active, resolve_mode, row_block
from cxxnet_tpu.ops.fused_epilogue import bias_act_reference, fused_bias_act
from cxxnet_tpu.ops.fused_lrn import fused_lrn, lrn_reference
from cxxnet_tpu.ops.fused_norm import bn_act_reference, fused_bn_act
from cxxnet_tpu.ops.fused_optim import fused_adam_apply, fused_sgd_apply
from cxxnet_tpu.trainer import Trainer

DTYPES = (jnp.float32, jnp.bfloat16)


def tol(dtype, f32, bf16):
    return f32 if dtype == jnp.float32 else bf16


def close(a, b, rtol, atol=None):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=rtol, atol=rtol if atol is None else atol)


# -- knob / selection plumbing ------------------------------------------------

def test_resolve_mode():
    assert resolve_mode("auto") == "auto"
    assert resolve_mode("1") == "on"
    assert resolve_mode("0") == "off"
    with pytest.raises(ConfigError):
        resolve_mode("sometimes")


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_kernels_active_modes(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.delenv("CXXNET_FUSED_KERNELS", raising=False)
    assert kernels_active("off") is False
    assert kernels_active("on") is True
    # auto selects a kernel only for a kind that has won a cell on the
    # chip: none has, so it is the references on every backend
    assert kernels_active("auto") is False
    # env switch beats an explicit config 'on' / 'off' / 'auto'
    monkeypatch.setenv("CXXNET_FUSED_KERNELS", "0")
    assert kernels_active("on") is False
    monkeypatch.setenv("CXXNET_FUSED_KERNELS", "1")
    assert kernels_active("off") is True
    assert kernels_active("auto") is True
    monkeypatch.setenv("CXXNET_FUSED_KERNELS", "auto")
    assert kernels_active("on") is False


def test_row_block():
    assert row_block(256) == 256
    assert row_block(2048, target=256) == 256
    assert row_block(24) == 24
    assert row_block(100) is None        # not a multiple of 8
    assert row_block(8 * 129, target=256) == 8 * 3  # largest 8k divisor


# -- fused batch norm ---------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("act", ["none", "relu"])
@pytest.mark.parametrize("two_pass", [False, True])
def test_bn_act_forward_parity(dtype, act, two_pass):
    key = jax.random.PRNGKey(0)
    x = (jax.random.normal(key, (8, 4, 4, 24)) * 2 + 1).astype(dtype)
    gamma = jax.random.normal(jax.random.fold_in(key, 1), (24,)) * 0.5 + 1
    beta = jax.random.normal(jax.random.fold_in(key, 2), (24,)) * 0.1
    ref = bn_act_reference(x, gamma, beta, 1e-5, act, two_pass)
    fused = fused_bn_act(x, gamma, beta, 1e-5, act, two_pass)
    assert fused is not None
    assert fused[0].dtype == x.dtype
    t = tol(dtype, 1e-5, 3e-2)
    for r, f in zip(ref, fused):
        close(r, f, t)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("act", ["none", "relu"])
def test_bn_act_grad_parity(dtype, act):
    key = jax.random.PRNGKey(1)
    x = (jax.random.normal(key, (8, 4, 4, 16)) * 2 - 0.5).astype(dtype)
    gamma = jax.random.normal(jax.random.fold_in(key, 1), (16,)) * 0.5 + 1
    beta = jax.random.normal(jax.random.fold_in(key, 2), (16,)) * 0.1

    def loss(fn):
        return lambda x, g, b: jnp.sum(
            fn(x, g, b, 1e-5, act)[0].astype(jnp.float32) ** 2)

    gr = jax.grad(loss(bn_act_reference), (0, 1, 2))(x, gamma, beta)
    gf = jax.grad(loss(fused_bn_act), (0, 1, 2))(x, gamma, beta)
    t = tol(dtype, 2e-4, 1e-1)
    for r, f in zip(gr, gf):
        assert r.dtype == f.dtype
        close(r, f, t)


def test_bn_unsupported_shape_falls_back():
    # rows not a multiple of 8 -> None (caller keeps the jnp reference)
    x = jnp.ones((3, 1, 1, 5), jnp.float32)
    assert fused_bn_act(x, jnp.ones((5,)), jnp.zeros((5,)), 1e-5) is None
    # int inputs are not a fused dtype
    xi = jnp.ones((8, 1, 1, 8), jnp.int32)
    assert fused_bn_act(xi, jnp.ones((8,)), jnp.zeros((8,)), 1e-5) is None


# -- fused LRN ----------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("nsize", [3, 5, 4])
def test_lrn_parity(dtype, nsize):
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 4, 4, 24)) \
        .astype(dtype)
    ref = lrn_reference(x, nsize, 0.001, 0.75, 1.0)
    fused = fused_lrn(x, nsize, 0.001, 0.75, 1.0)
    assert fused is not None and fused.dtype == x.dtype
    close(ref, fused, tol(dtype, 1e-5, 2e-2))
    gr = jax.grad(lambda x: jnp.sum(
        lrn_reference(x, nsize, 0.001, 0.75, 1.0).astype(jnp.float32) ** 2
    ))(x)
    gf = jax.grad(lambda x: jnp.sum(
        fused_lrn(x, nsize, 0.001, 0.75, 1.0).astype(jnp.float32) ** 2
    ))(x)
    close(gr, gf, tol(dtype, 5e-4, 5e-2))


def test_lrn_unsupported_falls_back():
    x = jnp.ones((8, 1, 1, 2048), jnp.float32)   # band > VMEM budget
    assert fused_lrn(x, 5, 1e-3, 0.75, 1.0) is None


# -- fused bias+act epilogue --------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("act,has_bias", [("relu", True), ("relu", False),
                                          ("none", True)])
def test_epilogue_parity(dtype, act, has_bias):
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (8, 4, 4, 24)).astype(dtype)
    b = (jax.random.normal(jax.random.fold_in(key, 1), (24,)) * 0.3
         if has_bias else None)
    ref = bias_act_reference(x, b, act)
    fused = fused_bias_act(x, b, act)
    assert fused is not None and fused.dtype == x.dtype
    close(ref, fused, 1e-6)
    if has_bias:
        gr = jax.grad(lambda x, b: jnp.sum(
            bias_act_reference(x, b, act).astype(jnp.float32) ** 2),
            (0, 1))(x, b)
        gf = jax.grad(lambda x, b: jnp.sum(
            fused_bias_act(x, b, act).astype(jnp.float32) ** 2),
            (0, 1))(x, b)
    else:
        gr = (jax.grad(lambda x: jnp.sum(
            bias_act_reference(x, None, act).astype(jnp.float32) ** 2))(x),)
        gf = (jax.grad(lambda x: jnp.sum(
            fused_bias_act(x, None, act).astype(jnp.float32) ** 2))(x),)
    for r, f in zip(gr, gf):
        close(r, f, tol(dtype, 1e-4, 2e-2))


def test_epilogue_nothing_to_fuse():
    x = jnp.ones((8, 1, 1, 8), jnp.float32)
    assert fused_bias_act(x, None, "none") is None


# -- fused multi-tensor optimizer apply ---------------------------------------

def _leaves(key):
    shapes = [(3, 5, 2, 7), (64,), (130,), (9, 11)]
    return [jax.random.normal(jax.random.fold_in(key, i), s)
            for i, s in enumerate(shapes)]


@pytest.mark.parametrize("nag", [False, True])
def test_fused_sgd_parity(nag):
    key = jax.random.PRNGKey(7)
    ws = _leaves(key)
    gs = [jax.random.normal(jax.random.fold_in(key, 10 + i), w.shape)
          for i, w in enumerate(ws)]
    gs[1] = gs[1].at[3].set(jnp.nan)         # NaN-zeroing clip semantics
    ms = [jnp.full_like(w, 0.1) for w in ws]
    lr, mu, wd, clip = 0.05, 0.9, 1e-4, 0.5
    nws, nms = fused_sgd_apply(ws, gs, ms, lr, mu, wd=wd, clip=clip,
                               nag=nag)
    for w, g, m, nw, nm in zip(ws, gs, ms, nws, nms):
        g = jnp.where(jnp.isnan(g), 0.0, g)
        g = jnp.clip(g, -clip, clip) + wd * w
        rm = mu * m - lr * g
        rw = w + ((1 + mu) * rm - mu * m if nag else rm)
        close(nw, rw, 1e-6)
        close(nm, rm, 1e-6)
        assert nw.shape == w.shape and nw.dtype == w.dtype


def test_fused_adam_parity():
    key = jax.random.PRNGKey(8)
    ws = _leaves(key)
    gs = [jax.random.normal(jax.random.fold_in(key, 20 + i), w.shape)
          for i, w in enumerate(ws)]
    m1s = [jnp.full_like(w, 0.02) for w in ws]
    m2s = [jnp.full_like(w, 0.03) for w in ws]
    lr, wd, clip, d1, d2, t = 0.01, 1e-4, 0.0, 0.1, 0.001, 3.0
    lr_t = lr * jnp.sqrt(1 - (1 - d2) ** t) / (1 - (1 - d1) ** t)
    nws, nm1, nm2 = fused_adam_apply(ws, gs, m1s, m2s, lr_t, wd=wd,
                                     clip=clip, d1=d1, d2=d2)
    for w, g, m1, m2, nw, n1, n2 in zip(ws, gs, m1s, m2s, nws, nm1, nm2):
        g = jnp.where(jnp.isnan(g), 0.0, g) + wd * w
        r1 = m1 + d1 * (g - m1)
        r2 = m2 + d2 * (jnp.square(g) - m2)
        rw = w - lr_t * r1 / (jnp.sqrt(r2) + 1e-8)
        close(nw, rw, 1e-6)
        close(n1, r1, 1e-6)
        close(n2, r2, 1e-6)


# -- trainer-level selection + parity -----------------------------------------

CONV_CFG = """
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
eta = 0.05
momentum = 0.9
wd = 0.0001
dev = cpu:0-0
eval_train = 0
"""


def _batch():
    rng = np.random.RandomState(0)
    return DataBatch(
        data=rng.rand(16, 8, 8, 3).astype(np.float32),
        label=rng.randint(0, 4, size=(16, 1)).astype(np.float32))


def _trainer(extra):
    tr = Trainer(parse_config_string(CONV_CFG + extra))
    tr.init_model()
    return tr


def _train_jaxpr(tr):
    b = _batch()

    def f(params, data, label):
        return tr.net.apply(params, tr.net_state, data, label, train=True,
                            rng=jax.random.PRNGKey(0)).loss
    return str(jax.make_jaxpr(f)(tr.params, jnp.asarray(b.data),
                                 jnp.asarray(b.label)))


def test_fused_selected_in_jaxpr():
    """The selection probe: with the knob forced on, the traced train
    forward contains the fused custom calls; with the escape hatch, and
    at the default, the jaxpr is reference-only."""
    assert "pallas_call" in _train_jaxpr(_trainer("fused_kernels = 1\n"))
    assert "pallas_call" not in _train_jaxpr(_trainer("fused_kernels = 0\n"))
    assert "pallas_call" not in _train_jaxpr(_trainer(""))


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

#: CONV_CFG's globals (eta, momentum, wd, dev, eval_train)
_TOY_TAIL = CONV_CFG.split("netconfig = end\n")[1]


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("net,kinds", [
    (BN_TOY, {"bn_act", "pool", "bias_act", "sgd_apply"}),
    (ALEX_TOY, {"bias_act", "lrn", "pool", "sgd_apply"})],
    ids=["conv_bn_relu", "alexnet_like"])
def test_default_train_step_is_xla_only(monkeypatch, backend, net, kinds):
    """The whole jitted train step (forward, backward, optimizer) at the
    default, ``fused_kernels = auto``: no Pallas kernel and no host
    callback of any kind in its jaxpr — whatever the backend says it is
    — and no site in the selection log under ``fused``. With
    ``fused_kernels = 1`` the same step holds the kernels of every kind
    the net reaches."""
    from cxxnet_tpu.ops.fused import selection_counts
    monkeypatch.delenv("CXXNET_FUSED_KERNELS", raising=False)

    def traced(extra):
        tr = Trainer(parse_config_string(net + _TOY_TAIL + extra))
        tr.init_model()
        step, args = tr._train_step_call(_batch())
        return str(jax.make_jaxpr(step)(*args)), \
            selection_counts(tr.net.fused_log)

    forced, by = traced("fused_kernels = 1\n")   # interpreted: before the
    assert "pallas_call" in forced                # backend is patched
    assert set(by["fused"]) == kinds
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    default, by = traced("")
    assert "pallas_call" not in default and "callback" not in default
    assert not by["fused"] and not by["reference"]


def test_env_escape_hatch(monkeypatch):
    monkeypatch.setenv("CXXNET_FUSED_KERNELS", "0")
    assert "pallas_call" not in _train_jaxpr(_trainer("fused_kernels = 1\n"))


def test_multi_device_mesh_keeps_fused_on():
    """Fused x mesh (ISSUE 9): a data-parallel mesh (the 8-CPU-device
    test default) no longer clears the fused gate — the kernels run as
    shard_map islands, so the traced step carries pallas_calls UNDER
    shard_map instead of silently taking the reference path."""
    cfg = CONV_CFG.replace("dev = cpu:0-0", "dev = cpu")
    tr = Trainer(parse_config_string(cfg + "fused_kernels = 1\n"))
    tr.init_model()
    assert tr.net._fused_now()
    assert tr.net.fused_spmd is not None
    assert tr.optimizer._fused_active()
    jx = _train_jaxpr(tr)
    assert "pallas_call" in jx and "shard_map" in jx


@pytest.mark.parametrize("updater,extra",
                         [("sgd", ""), ("nag", "updater = nag\n"),
                          ("adam", "updater = adam\neta = 0.002\n")])
def test_training_parity_fused_vs_reference(updater, extra):
    """Five full update steps (forward + backward + fused optimizer)
    must track the reference trajectory: losses and final params."""
    b = _batch()
    runs = {}
    for mode in ("0", "1"):
        tr = _trainer(extra + f"fused_kernels = {mode}\n")
        losses = []
        for _ in range(5):
            tr.update(b)
            losses.append(tr.last_loss)
        runs[mode] = (losses, jax.tree_util.tree_map(
            np.asarray, tr.mesh.gather(tr.params)))
    for l0, l1 in zip(runs["0"][0], runs["1"][0]):
        assert abs(l0 - l1) < 2e-3, (runs["0"][0], runs["1"][0])
    for a, b_ in zip(jax.tree_util.tree_leaves(runs["0"][1]),
                     jax.tree_util.tree_leaves(runs["1"][1])):
        np.testing.assert_allclose(a, b_, rtol=3e-3, atol=3e-3)


def test_training_parity_bf16():
    """bf16 compute policy: fused path must keep learning and track the
    reference within bf16 noise."""
    b = _batch()
    losses = {}
    for mode in ("0", "1"):
        tr = _trainer(f"compute_dtype = bfloat16\nfused_kernels = {mode}\n")
        ls = []
        for _ in range(5):
            tr.update(b)
            ls.append(tr.last_loss)
        losses[mode] = ls
    assert losses["1"][-1] < losses["1"][0]          # learning
    for l0, l1 in zip(losses["0"], losses["1"]):
        assert abs(l0 - l1) < 5e-2, losses


def test_act_fold_values_unchanged():
    """graph.act_fusion_plan folds bn->relu / conv->relu / fullc->relu;
    captured node values and the net output must be identical to an
    unfused run (post-activation values on the folded producers'
    nodes are the documented capture semantics)."""
    tr1 = _trainer("fused_kernels = 1\n")
    tr0 = _trainer("fused_kernels = 0\n")
    # same init seed -> identical params
    b = _batch()
    r1 = tr1.net.apply(tr1.params, tr1.net_state, jnp.asarray(b.data),
                       jnp.asarray(b.label), train=False)
    r0 = tr0.net.apply(tr0.params, tr0.net_state, jnp.asarray(b.data),
                       jnp.asarray(b.label), train=False)
    np.testing.assert_allclose(np.asarray(r1.out), np.asarray(r0.out),
                               rtol=2e-5, atol=2e-5)
    # the folded relus are recorded and their producers carry the act
    assert tr1.net._act_folded, "expected folded relu layers"
    assert set(tr1.net._fuse_act.values()) == {"relu"}


def test_bn_two_pass_knob():
    """bn_two_pass = 1 (ADVICE r5) is honored by both paths and changes
    nothing for well-conditioned inputs."""
    b = _batch()
    vals = []
    for mode in ("0", "1"):
        tr = _trainer(f"fused_kernels = {mode}\nbn_two_pass = 1\n")
        assert tr.net.layers[1].two_pass is True
        tr.update(b)
        vals.append(tr.last_loss)
    assert abs(vals[0] - vals[1]) < 2e-3
