"""Mixed-precision policy tests (compute_dtype = bfloat16 / float16):
fp32 master weights + optimizer state under every policy, bf16
activations/gradients inside the jitted std train step (jaxpr probe),
accuracy parity with fp32 on the synthetic-cluster task, the fp16
dynamic loss scaler's overflow skip/halve + growth, dtype-portable
checkpoints, and composition with train_chain / update_period. Reuses
the test_trainer.py harness."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cxxnet_tpu.config import parse_config_string, parse_policy, ConfigError
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.trainer import Trainer

from test_trainer import (MLP_CFG, SYN_ITER, eval_error, make_trainer,
                          synth_iter, train_rounds)

POLICIES = ("float32", "bfloat16", "float16")


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


# -- policy parsing ----------------------------------------------------------

def test_parse_policy_aliases_and_rejects():
    for name, want in (("float32", jnp.float32), ("fp32", jnp.float32),
                       ("bfloat16", jnp.bfloat16), ("bf16", jnp.bfloat16),
                       ("float16", jnp.float16), ("fp16", jnp.float16)):
        pol = parse_policy(name)
        assert pol.compute_dtype == want
        assert pol.param_dtype == jnp.float32
        assert pol.output_dtype == jnp.float32
    assert parse_policy("float16").needs_loss_scale
    assert not parse_policy("bfloat16").needs_loss_scale
    assert not parse_policy("float32").reduced
    assert parse_policy("bf16").reduced
    with pytest.raises(ConfigError):
        parse_policy("int8")


# -- masters stay fp32 under every policy ------------------------------------

@pytest.mark.parametrize("dtype", POLICIES)
def test_masters_stay_fp32(mesh8, dtype):
    tr = make_trainer(mesh8, extra=f"compute_dtype = {dtype}\n")
    itr = synth_iter()
    for b in itr:
        tr.update(b)
        break
    for leaf in _leaves(tr.params):
        assert np.asarray(leaf).dtype == np.float32
    mom = {k: v for k, v in tr.opt_state.items() if k != "_mp"}
    for leaf in _leaves(mom):
        assert np.asarray(leaf).dtype == np.float32
    # the loss value stays an fp32 reduction under every policy
    assert np.asarray(tr._last_loss).dtype == np.float32
    # the scaler subtree exists exactly for fp16
    assert ("_mp" in tr.opt_state) == (dtype == "float16")


# -- bf16 interior: jaxpr + node-dtype probe ---------------------------------

def _iter_eqns(jaxpr):
    """All eqns of a jaxpr including nested call/scan/cond sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for x in vs:
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    yield from _iter_eqns(sub)


def test_bf16_std_step_intermediates_are_bf16(mesh8):
    """With compute_dtype = bfloat16 every matmul in the std train step's
    forward AND backward runs on bf16 operands, while the loss value and
    every parameter gradient leaf come back fp32 (the per-param cast's
    transpose upcasts — grads meet the fp32 optimizer in fp32)."""
    tr = make_trainer(mesh8, extra="compute_dtype = bfloat16\n")
    net = tr.net
    key = jax.random.PRNGKey(0)
    params, state = net.init(key)
    data = jnp.zeros((16, 1, 1, 16), jnp.float32)
    label = jnp.zeros((16, 1), jnp.float32)
    mask = jnp.ones((16,), jnp.float32)

    def fwd_bwd(p):
        def loss_fn(p):
            return net.apply(p, state, data, label, mask, rng=key,
                             train=True).loss
        return jax.value_and_grad(loss_fn)(p)

    jaxpr = jax.make_jaxpr(fwd_bwd)(params)
    dots = [e for e in _iter_eqns(jaxpr.jaxpr)
            if e.primitive.name in ("dot_general", "conv_general_dilated")]
    assert len(dots) >= 4, "expected fwd+bwd matmuls in the step jaxpr"
    for e in dots:
        for v in e.invars:
            assert v.aval.dtype == jnp.bfloat16, (
                f"{e.primitive.name} operand is {v.aval.dtype}, "
                f"expected bf16: {e}")
    loss_aval, grads_avals = jaxpr.out_avals[0], jaxpr.out_avals[1:]
    assert loss_aval.dtype == jnp.float32
    for a in grads_avals:
        assert a.dtype == jnp.float32
    # forward node values (the activations flowing between layers) are
    # bf16 for the hidden chain; the softmax prediction node is fp32 by
    # design (loss precision stays fp32)
    res = net.apply(params, state, data, label, mask, rng=key, train=True,
                    capture_nodes=True)
    assert res.nodes["h1"].dtype == jnp.bfloat16
    assert res.nodes["a1"].dtype == jnp.bfloat16
    assert res.nodes["out"].dtype == jnp.float32   # post-softmax
    assert res.loss.dtype == jnp.float32


# -- accuracy parity ---------------------------------------------------------

def test_bf16_training_matches_fp32_accuracy(mesh8):
    """bf16 synthetic-cluster training lands in the same accuracy band as
    the fp32 run (test_trainer.test_training_learns_dp8's bar)."""
    tr = make_trainer(mesh8, extra="compute_dtype = bfloat16\n")
    itr = synth_iter()
    err0 = eval_error(tr, itr)
    train_rounds(tr, itr, 5)
    err1 = eval_error(tr, itr)
    assert err0 > 0.5
    assert err1 < 0.1, f"bf16 did not learn: {err0} -> {err1}"


def test_fp16_training_learns(mesh8):
    tr = make_trainer(mesh8, extra="compute_dtype = float16\n")
    itr = synth_iter()
    train_rounds(tr, itr, 5)
    err = eval_error(tr, itr)
    assert err < 0.1, f"fp16 did not learn: {err}"
    assert np.isfinite(float(tr.opt_state["_mp"]["scale"]))


# -- fp16 dynamic loss scaler ------------------------------------------------

def test_fp16_scaler_halves_and_skips_on_overflow(mesh8):
    """A forced-overflow step (batch values beyond fp16's 65504 ceiling
    blow the forward up to inf, so every gradient is inf/nan) must SKIP
    the apply — params bit-identical — and halve the scale; the next
    clean batch applies and training recovers with finite params."""
    tr = make_trainer(mesh8, extra="compute_dtype = float16\n")
    itr = synth_iter()
    batch = next(iter(itr))
    poisoned = DataBatch(data=np.full_like(np.asarray(batch.data), 1e8),
                         label=np.asarray(batch.label))
    w0 = tr.get_weight("fc1", "wmat").copy()
    s0 = float(tr.opt_state["_mp"]["scale"])
    tr.update(poisoned)
    s1 = float(tr.opt_state["_mp"]["scale"])
    assert s1 == s0 / 2, f"scale did not halve: {s0} -> {s1}"
    assert int(tr.opt_state["_mp"]["good"]) == 0
    np.testing.assert_array_equal(tr.get_weight("fc1", "wmat"), w0,
                                  err_msg="overflow step must skip apply")
    # recovery: the very next clean batch applies on finite masters
    tr.update(batch)
    w1 = tr.get_weight("fc1", "wmat")
    assert not np.array_equal(w1, w0), "clean step after overflow must apply"
    assert np.all(np.isfinite(w1)), "overflow corrupted the masters"
    assert float(tr.opt_state["_mp"]["scale"]) == s1   # unchanged until window
    for _ in range(3):
        tr.update(batch)
    assert np.isfinite(tr.last_loss)


def test_fp16_scaler_grows_after_window(mesh8):
    tr = make_trainer(
        mesh8,
        extra="compute_dtype = float16\nloss_scale_window = 2\n")
    itr = synth_iter()
    batch = next(iter(itr))
    s0 = float(tr.opt_state["_mp"]["scale"])
    tr.update(batch)
    assert float(tr.opt_state["_mp"]["scale"]) == s0
    tr.update(batch)          # second clean apply -> doubled, counter reset
    assert float(tr.opt_state["_mp"]["scale"]) == 2 * s0
    assert int(tr.opt_state["_mp"]["good"]) == 0


# -- checkpoints stay fp32 masters, policy-portable --------------------------

def test_checkpoint_bf16_run_restores_fp32_masters_bitexact(tmp_path, mesh8):
    tr = make_trainer(mesh8, extra="compute_dtype = bfloat16\n")
    itr = synth_iter()
    train_rounds(tr, itr, 2)
    path = str(tmp_path / "0001.model")
    tr.save_model(path)
    # same-policy reload: bit-exact fp32 masters
    tr2 = make_trainer(mesh8, extra="compute_dtype = bfloat16\n")
    tr2.load_model(path)
    for a, b in zip(_leaves(tr.mesh.gather(tr.params)),
                    _leaves(tr2.mesh.gather(tr2.params))):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype == np.float32
        np.testing.assert_array_equal(a, b)
    # cross-policy reload: the checkpoint is dtype-portable
    tr3 = make_trainer(mesh8)                       # fp32 policy
    tr3.load_model(path)
    np.testing.assert_array_equal(tr3.get_weight("fc1", "wmat"),
                                  tr.get_weight("fc1", "wmat"))
    tr3.update(next(iter(synth_iter())))


def test_checkpoint_fp16_scaler_adapts_across_policies(tmp_path, mesh8):
    tr = make_trainer(mesh8, extra="compute_dtype = float16\n")
    itr = synth_iter()
    for b in itr:
        tr.update(b)
        break
    path = str(tmp_path / "fp16.model")
    tr.save_model(path)
    # fp16 -> fp32: the "_mp" subtree is dropped on load
    tr32 = make_trainer(mesh8)
    tr32.load_model(path)
    assert "_mp" not in tr32.opt_state
    tr32.update(next(iter(synth_iter())))
    # fp32 checkpoint -> fp16 trainer: a fresh scaler is injected
    path32 = str(tmp_path / "fp32.model")
    tr32.save_model(path32)
    tr16 = make_trainer(mesh8, extra="compute_dtype = float16\n")
    tr16.load_model(path32)
    assert "_mp" in tr16.opt_state
    tr16.update(next(iter(synth_iter())))


# -- composition: train_chain + update_period --------------------------------

@pytest.mark.parametrize("dtype", ("bfloat16", "float16"))
def test_chain_batches_match_sequential_reduced(mesh8, dtype):
    """update_chain_batches under a reduced policy reproduces sequential
    update() (same op sequence -> same roundings on CPU)."""
    extra = f"compute_dtype = {dtype}\neval_train = 0\n"
    tr_c = make_trainer(mesh8, extra=extra)
    tr_s = make_trainer(mesh8, extra=extra)
    batches = list(synth_iter())[:3]
    losses = np.asarray(tr_c.update_chain_batches(batches))
    seq = []
    for b in batches:
        tr_s.update(b)
        seq.append(float(tr_s.last_loss))
    assert np.all(np.isfinite(losses))
    np.testing.assert_allclose(losses, seq, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(tr_c.get_weight("fc1", "wmat"),
                               tr_s.get_weight("fc1", "wmat"),
                               rtol=1e-3, atol=1e-4)
    if dtype == "float16":
        assert (float(tr_c.opt_state["_mp"]["scale"])
                == float(tr_s.opt_state["_mp"]["scale"]))


@pytest.mark.parametrize("dtype", ("bfloat16", "float16"))
def test_update_period_composes_with_reduced(mesh8, dtype):
    """update_period accumulation under a reduced policy: the accumulator
    stays fp32 and two half-steps land one combined apply."""
    tr = make_trainer(
        mesh8, extra=f"compute_dtype = {dtype}\nupdate_period = 2\n")
    batches = list(synth_iter())[:2]
    w0 = tr.get_weight("fc1", "wmat").copy()
    tr.update(batches[0])                 # mid-period: no apply yet
    for leaf in _leaves(tr.accum):
        assert np.asarray(leaf).dtype == np.float32
    np.testing.assert_array_equal(tr.get_weight("fc1", "wmat"), w0)
    tr.update(batches[1])                 # boundary: apply
    w1 = tr.get_weight("fc1", "wmat")
    assert not np.array_equal(w1, w0)
    assert np.all(np.isfinite(w1))


def test_chain_with_update_period_fp16(mesh8):
    """The accumulating chain (update_period riding the scan carry)
    composes with the fp16 scaler riding opt_state."""
    extra = "compute_dtype = float16\nupdate_period = 2\n"
    tr = make_trainer(mesh8, extra=extra)
    batches = list(synth_iter())[:4]
    losses = np.asarray(tr.update_chain_batches(batches))
    assert np.all(np.isfinite(losses))
    assert tr.epoch_counter == 2
    assert np.all(np.isfinite(tr.get_weight("fc1", "wmat")))


# -- BN variance cancellation: clamped in silence, seen by health = 1 ------

def _bn_net():
    from cxxnet_tpu.graph import build_graph
    from cxxnet_tpu.model import Network
    g = build_graph(parse_config_string(
        "netconfig=start\nlayer[0->1] = batch_norm:bn\nnetconfig=end\n"
        "input_shape = 4,6,6\n"))
    return Network(g, g.defcfg)


def _bn_var_min(net, x):
    params, state = net.init(jax.random.PRNGKey(0))
    res = net.apply(params, state, jnp.asarray(x), train=True, rng=None,
                    health=True)
    return float(res.health["bn"]["bn_var_min"])


def test_bn_variance_cancellation_reads_zero_in_the_health_tap():
    """A large-mean/low-variance input cancels the one-pass E[x^2]-E[x]^2
    moment negative; the BN path clamps it to 0 in silence (no host
    callback in the step), and ``health = 1`` is where it shows: the
    tap's ``bn_var_min`` reads 0, at or under ``health_bn_var_floor`` —
    a hit for the probe's ``bn_collapse`` window rule
    (telemetry/modelhealth.py). A benign input reads well over it."""
    from cxxnet_tpu.config import HealthConfig
    floor = HealthConfig().bn_var_floor
    # fp32 cancellation, deterministic: constant 99999 has zero true
    # variance, but fl(mean(x^2)) - fl(mean(x))^2 rounds to -40960 (the
    # ~1e10 squares carry ~1e3-1e4 of fp32 rounding)
    x = np.full((8, 6, 6, 4), 99999.0, np.float32)
    assert _bn_var_min(_bn_net(), x) == 0.0 <= floor
    benign = np.random.RandomState(1).randn(8, 6, 6, 4).astype(np.float32)
    assert _bn_var_min(_bn_net(), benign) > 0.1 > floor


# -- serving dtype override --------------------------------------------------

def test_engine_dtype_override(mesh8):
    """An fp32-trained net serves under a bf16 engine: predictions agree
    with the fp32 engine on confidently-classified inputs and raw
    outputs come back fp32."""
    from cxxnet_tpu.serve.engine import InferenceEngine
    tr = make_trainer(mesh8)
    itr = synth_iter()
    train_rounds(tr, itr, 3)
    eng32 = InferenceEngine(tr, buckets="8", max_batch=8, layout="NHWC")
    engbf = InferenceEngine(tr, buckets="8", max_batch=8, layout="NHWC",
                            dtype="bfloat16")
    assert engbf.compute_dtype == jnp.bfloat16
    itr.before_first()
    rows = np.asarray(itr.next().data)[:8].reshape(8, -1)
    p32, pbf = eng32.predict(rows), engbf.predict(rows)
    np.testing.assert_array_equal(p32, pbf)
    raw = engbf.predict_raw(rows)
    assert raw.dtype == np.float32
    assert np.all(np.isfinite(raw))
