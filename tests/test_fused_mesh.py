"""Fused kernels x meshes (ISSUE 9 tentpole): shard_map islands.

Pins the acceptance criteria: on a dp mesh with fused_kernels=1
(interpret mode on CPU) the step jaxpr contains the fused pallas_calls
under shard_map, fused BN moments equal the unsharded global-moment
reference with fp32 BIT parity (integer-valued activations make the
sums exact, so any association must give identical bits — a
shard-local-moment bug would be off by whole orders), the trainer no
longer clears the fused gate for dp/sp meshes, and fallbacks are
counted in cxxnet_fused_fallback_total{reason}.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from cxxnet_tpu.config import parse_config_string
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.ops.fused import FusedSpmd
from cxxnet_tpu.ops.fused_epilogue import bias_act_reference, fused_bias_act
from cxxnet_tpu.ops.fused_norm import bn_act_reference, fused_bn_act
from cxxnet_tpu.parallel import make_mesh_context
from cxxnet_tpu.trainer import Trainer

pytestmark = pytest.mark.quick


def _mesh_ctx(n=8, mp=1):
    return make_mesh_context(devices=jax.devices()[:n], model_parallel=mp)


def _spmd(ctx):
    return FusedSpmd(mesh=ctx.mesh, batch_axis=ctx.data_axis)


def _int_batch(shape, lo=0, hi=64, scale=0.125, seed=0):
    """f32 data whose values (and squares) sum EXACTLY in f32: bitwise
    moment parity then holds regardless of reduction association."""
    r = np.random.RandomState(seed)
    return (r.randint(lo, hi, shape) * scale).astype(np.float32)


def test_mesh_bn_bit_parity_and_grads():
    """Fused BN on the dp mesh: psum'd moments == unsharded
    global-moment reference bit-for-bit (fp32, exact sums); y
    bit-equal; grads match the jnp reference."""
    ctx = _mesh_ctx()
    spmd = _spmd(ctx)
    x = jnp.asarray(_int_batch((16, 4, 8, 8)))
    gamma = jnp.asarray(np.linspace(0.5, 1.5, 8), jnp.float32)
    beta = jnp.asarray(np.linspace(-0.2, 0.3, 8), jnp.float32)
    xs = jax.device_put(x, NamedSharding(ctx.mesh, P("data")))

    @jax.jit
    def fwd(x, g, b):
        return fused_bn_act(x, g, b, eps=1e-5, act="relu", spmd=spmd)
    y, mean, var = fwd(xs, gamma, beta)
    y_ref, mean_ref, var_ref = bn_act_reference(x, gamma, beta, 1e-5,
                                                act="relu")
    # the acceptance bit-parity claim is about the MOMENTS (sync-BN):
    # exact sums -> any association gives identical bits, so a
    # shard-local-moment bug cannot hide inside a tolerance
    assert np.array_equal(np.asarray(mean), np.asarray(mean_ref))
    assert np.array_equal(np.asarray(var), np.asarray(var_ref))
    # y differs from the jnp path only by XLA's FMA contraction of the
    # scale/shift chain (same reason the single-device suite compares
    # with allclose) — identical moments, elementwise-rounding-tight
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6)

    def loss_fused(g, b, x):
        y, _, _ = fused_bn_act(x, g, b, eps=1e-5, act="relu", spmd=spmd)
        return jnp.sum(y * jnp.cos(y))

    def loss_ref(g, b, x):
        y, _, _ = bn_act_reference(x, g, b, 1e-5, act="relu")
        return jnp.sum(y * jnp.cos(y))
    gf = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(gamma, beta, xs)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(gamma, beta, x)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_mesh_bn_jaxpr_pallas_under_shard_map():
    ctx = _mesh_ctx()
    spmd = _spmd(ctx)
    x = jnp.zeros((16, 4, 8, 8), jnp.float32)
    g = jnp.ones((8,), jnp.float32)
    jx = str(jax.make_jaxpr(
        lambda x, g: fused_bn_act(x, g, g, 1e-5, spmd=spmd))(x, g))
    # the pallas_calls appear INSIDE the shard_map eqn's body
    assert "shard_map" in jx
    inner = jx[jx.index("shard_map"):]
    assert "pallas_call" in inner and "psum" in inner


def test_mesh_epilogue_grads_include_dbias_psum():
    """Bias epilogue island: dbias is the cross-shard sum (psum) —
    compare values and grads against the jnp reference."""
    ctx = _mesh_ctx()
    spmd = _spmd(ctx)
    x = jnp.asarray(_int_batch((8, 2, 4, 8), lo=-32, hi=32))
    bias = jnp.asarray(np.linspace(-1, 1, 8), jnp.float32)
    xs = jax.device_put(x, NamedSharding(ctx.mesh, P("data")))
    y = jax.jit(lambda x, b: fused_bias_act(x, b, "relu",
                                            spmd=spmd))(xs, bias)
    assert np.array_equal(np.asarray(y),
                          np.asarray(bias_act_reference(x, bias, "relu")))

    def lf(b, x):
        return jnp.sum(fused_bias_act(x, b, "relu", spmd=spmd) ** 2)

    def lr(b, x):
        return jnp.sum(bias_act_reference(x, b, "relu") ** 2)
    db_f, dx_f = jax.jit(jax.grad(lf, argnums=(0, 1)))(bias, xs)
    db_r, dx_r = jax.jit(jax.grad(lr, argnums=(0, 1)))(bias, x)
    np.testing.assert_allclose(np.asarray(db_f), np.asarray(db_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dx_f), np.asarray(dx_r),
                               rtol=1e-5, atol=1e-5)


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
compute_dtype = float32
"""


def _batch(seed=0):
    r = np.random.RandomState(seed)
    return DataBatch(
        data=(r.randint(0, 16, (8, 8, 8, 3)) * 0.25).astype(np.float32),
        label=r.randint(0, 4, (8, 1)).astype(np.float32))


def _run(tr, steps=5, seed=0):
    losses = []
    for _ in range(steps):
        losses.append((tr.update(_batch(seed)), float(tr.last_loss))[1])
    return losses


def test_trainer_dp_mesh_keeps_fused_and_matches_single_device():
    """Gate acceptance: the dp-mesh trainer keeps fused_kernels=1 ON
    (islands), its step jaxpr carries pallas under shard_map, and a
    5-step run tracks the single-device fused run."""
    cfg = parse_config_string(CONV_CFG + "fused_kernels = 1\n")
    tr_m = Trainer(cfg, mesh_ctx=_mesh_ctx())
    tr_m.init_model()
    assert tr_m.net._fused_now() and tr_m.net.fused_spmd is not None
    assert tr_m.optimizer._fused_active()
    assert tr_m.optimizer.fused_spmd is not None
    tr_1 = Trainer(cfg, mesh_ctx=_mesh_ctx(n=1))
    tr_1.init_model()
    lm, l1 = _run(tr_m), _run(tr_1)
    for a, b in zip(lm, l1):
        assert abs(a - b) < 5e-3, (lm, l1)


@pytest.mark.parametrize("knob,counted", [("fused_kernels = 1\n", 1),
                                          ("", 0)], ids=["on", "auto"])
def test_trainer_pp_mesh_still_clears_with_counter(monkeypatch, knob,
                                                   counted):
    """Topologies the islands do not cover (pp) still clear the gate —
    with the cxxnet_fused_fallback_total{reason} counter bumped under
    ``fused_kernels = 1``; under ``auto`` no kernel was selected, so
    nothing is lost and nothing is counted, on a TPU backend either."""
    from cxxnet_tpu.telemetry.registry import get_registry
    monkeypatch.delenv("CXXNET_FUSED_KERNELS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fam = get_registry().counter(
        "cxxnet_fused_fallback_total",
        "fused kernel suite fallbacks to the reference path, by reason",
        labels=("reason",))
    before = fam.labels("pipeline_parallel").value
    cfg = parse_config_string(
        CONV_CFG.replace("layer[5->6] = fullc:fc",
                         "layer[5->6] = fullc:fc\n  stage = 1")
        + knob + "pipeline_parallel = 2\n")
    tr = Trainer(cfg, mesh_ctx=make_mesh_context(
        devices=jax.devices()[:2], pipeline_parallel=2))
    assert not tr.net._fused_now()
    assert not tr.optimizer._fused_active()
    assert fam.labels("pipeline_parallel").value == before + counted


def test_sp_mesh_keeps_fused_optimizer():
    """sp meshes keep the gate open (the step body is already manual);
    sp x tp clears it (model axis stays automatic inside)."""
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
fused_kernels = 1
eval_train = 0
""")
    tr = Trainer(lm_cfg, mesh_ctx=make_mesh_context(
        devices=jax.devices()[:2], seq_parallel=2))
    assert tr.net._fused_now() and tr.optimizer._fused_active()
    r = np.random.RandomState(0)
    b = DataBatch(data=r.randint(0, 8, (8, 1, 1, 16)).astype(np.float32),
                  label=r.randint(0, 8, (8, 16)).astype(np.float32))
    tr.init_model()
    tr.update(b)            # fused multi-tensor optimizer inside the
    assert np.isfinite(float(tr.last_loss))   # manual sp step body
    tr2 = Trainer(lm_cfg, mesh_ctx=make_mesh_context(
        devices=jax.devices()[:4], seq_parallel=2, model_parallel=2))
    assert not tr2.net._fused_now()


def test_shape_fallback_is_counted():
    """An op-level shape-gate fallback on a mesh is visible in the
    counter (satellite: no silent slow path)."""
    from cxxnet_tpu.telemetry.registry import get_registry
    ctx = _mesh_ctx()
    spmd = _spmd(ctx)
    fam = get_registry().counter(
        "cxxnet_fused_fallback_total",
        "fused kernel suite fallbacks to the reference path, by reason",
        labels=("reason",))
    before = fam.labels("bn_batch_indivisible").value
    x = jnp.zeros((6, 4, 8, 8), jnp.float32)      # 6 rows % 8 shards != 0
    g = jnp.ones((8,), jnp.float32)
    assert fused_bn_act(x, g, g, 1e-5, spmd=spmd) is None
    assert fam.labels("bn_batch_indivisible").value == before + 1
