"""Model-health observability (doc/tasks.md "Model health"): in-trace
per-layer numerics vs a numpy reference, the zero-overhead off
contract (jaxpr identity + no host syncs), sync amortization, NaN
provenance under fp32 and the fp16 scaler path, the training-dynamics
detectors, dp-mesh stat consistency, the config namespace, the report
section, and the offline ckpt_health verdicts."""

import json
import os

import jax
import numpy as np
import pytest

from cxxnet_tpu.config import (ConfigError, parse_config_string,
                               parse_health_config)
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.parallel import make_mesh_context
from cxxnet_tpu.telemetry.modelhealth import (HealthProbe, WindowRule,
                                              diagnose_nonfinite)
from cxxnet_tpu.telemetry.registry import MetricRegistry
from cxxnet_tpu.trainer import Trainer

CFG = """
netconfig=start
layer[+1:h1] = fullc:fc1
  nhidden = 16
  random_type = xavier
layer[+1:a1] = relu
layer[a1->out] = fullc:fc2
  nhidden = 4
  random_type = xavier
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.1
dev = cpu
eval_train = 0
"""


def make_trainer(extra="", ndev=1):
    ctx = make_mesh_context(devices=jax.devices()[:ndev])
    tr = Trainer(parse_config_string(CFG + extra), mesh_ctx=ctx)
    tr.init_model()
    return tr


def make_batch(seed=0):
    rs = np.random.RandomState(seed)
    return DataBatch(data=rs.randn(16, 1, 1, 8).astype(np.float32),
                     label=rs.randint(0, 4, (16, 1)).astype(np.float32))


def _gather_np(tr, tree):
    return jax.tree_util.tree_map(np.asarray, tr.mesh.gather(tree))


def test_stats_match_numpy_reference():
    """grad/param/update/activation numbers equal an independent
    jax.grad + numpy recomputation of the same step."""
    tr = make_trainer("health = 1\n")
    b = make_batch()
    before = _gather_np(tr, tr.params)
    tr.update(b)
    h = jax.device_get(tr.last_health_handle)
    after = _gather_np(tr, tr.params)
    # independent grads of the exact same forward
    net = tr.net
    rng = jax.random.fold_in(tr._base_key, 0)
    mask = np.ones((16,), np.float32)

    def loss_fn(p):
        res = net.apply(p, {}, b.data, b.label, mask, rng=rng,
                        train=True)
        return res.loss
    grads = jax.tree_util.tree_map(np.asarray,
                                   jax.grad(loss_fn)(before))
    sq = 0.0
    for lname, lp in grads.items():
        for tag, g in lp.items():
            st = h["grad"][f"{lname}/{tag}"]
            np.testing.assert_allclose(
                st["rms"], np.sqrt(np.mean(np.square(g))), rtol=1e-5)
            np.testing.assert_allclose(st["absmax"], np.max(np.abs(g)),
                                       rtol=1e-5)
            assert float(st["finite_frac"]) == 1.0
            sq += float(np.sum(np.square(g, dtype=np.float64)))
    np.testing.assert_allclose(h["grad_norm"], np.sqrt(sq), rtol=1e-5)
    assert float(h["grad_finite"]) == 1.0
    for lname, lp in after.items():
        for tag, w in lp.items():
            key = f"{lname}/{tag}"
            np.testing.assert_allclose(
                h["param"][key]["rms"],
                np.sqrt(np.mean(np.square(w))), rtol=1e-5)
            d = w - before[lname][tag]
            np.testing.assert_allclose(
                h["update"][key]["ratio"],
                np.sqrt(np.mean(np.square(d)))
                / (np.sqrt(np.mean(np.square(before[lname][tag])))
                   + 1e-12), rtol=1e-4)
    # activation taps: relu dead fraction + abs-max vs a plain forward
    nodes = jax.jit(lambda p: net.apply(p, {}, b.data, b.label, mask,
                                        rng=rng, train=True,
                                        capture_nodes=True).nodes)(before)
    a1 = np.asarray(nodes["a1"])
    np.testing.assert_allclose(h["act"]["relu_1"]["zero_frac"],
                               np.mean(a1 == 0.0), rtol=1e-6)
    np.testing.assert_allclose(h["act"]["relu_1"]["absmax"],
                               np.max(np.abs(a1)), rtol=1e-6)


def test_bn_var_min_tap():
    """batch_norm layers report the minimum per-channel batch variance
    of their INPUT (the collapse-to-zero early-warning signal)."""
    cfg = """
netconfig=start
layer[+1:h1] = fullc:fc1
  nhidden = 8
  random_type = xavier
layer[+1:b1] = batch_norm:bn1
layer[+1:o1] = fullc:fc2
  nhidden = 4
  random_type = xavier
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.1
dev = cpu
eval_train = 0
health = 1
"""
    tr = Trainer(parse_config_string(cfg))
    tr.init_model()
    b = make_batch()
    before = _gather_np(tr, tr.params)
    tr.update(b)
    h = jax.device_get(tr.last_health_handle)
    rng = jax.random.fold_in(tr._base_key, 0)
    nodes = jax.jit(lambda p: tr.net.apply(
        p, _gather_np(tr, tr.net_state), b.data, b.label,
        np.ones((16,), np.float32), rng=rng, train=True,
        capture_nodes=True).nodes)(before)
    # the BN layer's INPUT is fc1's output node h1
    x = np.asarray(nodes["h1"], np.float64).reshape(16, -1)
    var = np.maximum(np.mean(x * x, 0) - np.mean(x, 0) ** 2, 0.0)
    np.testing.assert_allclose(h["act"]["bn1"]["bn_var_min"],
                               var.min(), rtol=1e-4)


def _lower_text(tr, b):
    step = tr._get_train_step(True, b)
    staged = tr.stage_batch(b)
    mask = tr._mask(b)
    rng = jax.random.fold_in(tr._base_key, 0)
    return step.lower(tr.params, tr.opt_state, tr.net_state, {},
                      staged.data, staged.label, mask,
                      tuple(staged.extra_data), rng,
                      tr._sched_scalars()).as_text()


def test_health_off_jaxpr_identity():
    """The zero-overhead contract: health=0 lowers to EXACTLY the
    program of a build that never saw the namespace; health=1 is a
    different (bigger) program but with identical training math."""
    b = make_batch()
    t_absent = _lower_text(make_trainer(), b)
    t_off = _lower_text(make_trainer("health = 0\n"), b)
    t_on = _lower_text(make_trainer("health = 1\n"), b)
    assert t_off == t_absent
    assert t_on != t_off and len(t_on) > len(t_off)


@pytest.mark.parametrize("extra", ["", "compute_dtype = bfloat16\n"])
def test_health_on_training_parity(extra):
    """health=1 must not change the training trajectory — losses and
    params bit-identical to the off run, under either compute dtype."""
    tra = make_trainer("health = 1\n" + extra)
    trb = make_trainer(extra)
    b = make_batch()
    for _ in range(4):
        tra.update(b)
        trb.update(b)
    assert float(tra.last_loss) == float(trb.last_loss)
    pa, pb = _gather_np(tra, tra.params), _gather_np(trb, trb.params)
    for (ka, la), (kb, lb) in zip(sorted(pa.items()),
                                  sorted(pb.items())):
        for tag in la:
            np.testing.assert_array_equal(la[tag], lb[tag])


def test_chain_dispatch_carries_health():
    """update_chain_batches (std multi chain) returns the LAST step's
    health tree; math unchanged vs sequential updates."""
    tra = make_trainer("health = 1\n")
    trb = make_trainer("health = 1\n")
    b1, b2 = make_batch(1), make_batch(2)
    tra.update_chain_batches([b1, b2])
    trb.update(b1)
    trb.update(b2)
    ha = jax.device_get(tra.last_health_handle)
    hb = jax.device_get(trb.last_health_handle)
    np.testing.assert_allclose(ha["grad_norm"], hb["grad_norm"],
                               rtol=1e-5)
    for key in hb["update"]:
        np.testing.assert_allclose(ha["update"][key]["ratio"],
                                   hb["update"][key]["ratio"],
                                   rtol=1e-4)


def test_sync_amortization_learn_task(tmp_path):
    """<= 1 host sync per health_interval (the steptime pin pattern):
    5 rounds x 8 steps at interval 8 -> exactly 5 probe syncs, and the
    off run takes zero."""
    from cxxnet_tpu.main import LearnTask
    base = f"""
data = train
iter = synthetic
  num_inst = 256
  num_class = 4
  input_shape = 1,1,8
  seed_data = 3
iter = end
{CFG}
model_dir = {tmp_path}
num_round = 5
save_model = 0
print_step = 0
silent = 1
"""
    task = LearnTask(parse_config_string(base + "health = 1\n"))
    task.task_train()
    steps = task.trainer._step_count
    assert task.health_probe is not None
    assert 1 <= task.health_probe.syncs <= steps // 8
    assert task.health_probe.last_grad_norm is not None
    task_off = LearnTask(parse_config_string(base))
    task_off.task_train()
    assert task_off.health_probe is None
    assert task_off.trainer.last_health_handle is None


def test_provenance_param_fp32():
    tr = make_trainer("health = 1\n")
    tr.update(make_batch())
    w = np.array(tr.get_weight("fc2", "wmat"))
    w[:] = np.nan
    tr.set_weight(w, "fc2", "wmat")
    prov = diagnose_nonfinite(tr)
    assert prov == "layer=fc2 kind=param leaf=wmat", prov


def test_provenance_activation_overflow():
    tr = make_trainer("health = 1\n")
    tr.update(make_batch())
    w = np.array(tr.get_weight("fc1", "wmat"))
    w[:] = 1e38                      # finite weights, inf activations
    tr.set_weight(w, "fc1", "wmat")
    prov = diagnose_nonfinite(tr)
    assert prov is not None and prov.startswith(
        "layer=fc1 kind=activation"), prov


def test_provenance_fp16_scaler_path():
    """fp16 scaler overflow: loss finite, apply skipped — the walk
    re-runs the backward WITH the live loss scale and names the first
    overflowing gradient."""
    tr = make_trainer("health = 1\ncompute_dtype = float16\n"
                      "loss_scale_init = 1073741824\n"
                      "loss_scale_max = 1073741824\n")
    tr.update(make_batch())
    h = jax.device_get(tr.last_health_handle)
    assert float(h["grad_finite"]) == 0.0      # the overflow happened
    assert float(h["loss_scale"]) < 1073741824  # and the scaler halved
    prov = diagnose_nonfinite(tr)
    assert prov is not None and " kind=grad " in prov + " ", prov
    assert prov.startswith("layer=fc"), prov


def test_provenance_named_layer_fp16(monkeypatch):
    """The device.step injection confined to one named layer is found
    under the fp16 policy too (pass 1 needs no batch stash)."""
    from cxxnet_tpu.resilience import failpoints
    tr = make_trainer("health = 1\ncompute_dtype = float16\n")
    monkeypatch.setenv("CXXNET_NAN_LAYER", "fc2")
    failpoints.set("device.step", "once")
    try:
        tr.update(make_batch())
    finally:
        failpoints.clear("device.step")
    prov = diagnose_nonfinite(tr)
    assert prov is not None and prov.startswith("layer=fc2 kind=param")


def test_window_rule_dedup_and_rearm():
    r = WindowRule(3)
    assert [r.observe("a", True) for _ in range(5)] == \
        [False, False, True, False, False]
    assert r.observe("a", False) is False      # recovery re-arms
    assert [r.observe("a", True) for _ in range(3)] == \
        [False, False, True]
    # None = skipped observation: streak neither advances nor resets
    r2 = WindowRule(2)
    assert r2.observe("k", True) is False
    assert r2.observe("k", None) is False
    assert r2.observe("k", True) is True


def test_dead_relu_detector_fires_once(tmp_path):
    """A crafted dead-ReLU net (relu input biased hard negative) trips
    the windowed detector exactly once, with a health_advice ledger
    event naming the relu layer."""
    from cxxnet_tpu.telemetry.ledger import LEDGER
    tr = make_trainer("health = 1\n")
    b0 = np.array(tr.get_weight("fc1", "bias"))
    b0[:] = -100.0
    tr.set_weight(b0, "fc1", "bias")
    cfg = parse_health_config([("health", "1"), ("health_window", "2")])
    probe = HealthProbe(cfg, registry=MetricRegistry(), silent=True)
    path = str(tmp_path / "ledger.jsonl")
    LEDGER.enable(path, "test-run")
    try:
        b = make_batch()
        for i in range(4):
            tr.update(b)
            probe.ingest(tr.last_health_handle, round_no=0, step=i)
    finally:
        LEDGER.disable()
    evs = [json.loads(l) for l in open(path)]
    advice = [e for e in evs if e["event"] == "health_advice"
              and e["kind"] == "dead_relu"]
    assert len(advice) == 1, advice
    assert advice[0]["layer"] == "relu_1"
    assert advice[0]["value"] == 1.0
    assert probe.last is not None \
        and probe.last["dead_max"][0] == 1.0


def test_dp_mesh_fleet_consistent_stats():
    """A dp-mesh run's health tree matches the single-device run's —
    the GSPMD step computes stats on the global logical arrays, so
    fleet consistency is by construction (pinned here)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    b = make_batch()
    tr1 = make_trainer("health = 1\n", ndev=1)
    tr2 = make_trainer("health = 1\n", ndev=2)
    tr1.update(b)
    tr2.update(b)
    h1 = jax.device_get(tr1.last_health_handle)
    h2 = jax.device_get(tr2.last_health_handle)
    l1 = jax.tree_util.tree_leaves(h1)
    l2 = jax.tree_util.tree_leaves(h2)
    assert len(l1) == len(l2)
    for a, c in zip(l1, l2):
        np.testing.assert_allclose(np.float64(a), np.float64(c),
                                   rtol=1e-4, atol=1e-7)


def test_sp_step_carries_reduced_health():
    """The sequence-parallel (manual shard_map) step returns a health
    tree whose activation stats were explicitly reduced across shards
    — grad stats match the sp=1 run of the same model."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from tests.test_seq_parallel import ITER_CFG, LM_CFG
    from cxxnet_tpu.io.data import create_iterator

    def mk(sp):
        ctx = make_mesh_context(devices=jax.devices()[:2],
                                seq_parallel=sp)
        tr = Trainer(parse_config_string(LM_CFG + "health = 1\n"),
                     mesh_ctx=ctx)
        tr.init_model()
        return tr
    tr1, tr2 = mk(1), mk(2)
    b = next(iter(create_iterator(parse_config_string(ITER_CFG))))
    tr1.update(b)
    tr2.update(b)
    h1 = jax.device_get(tr1.last_health_handle)
    h2 = jax.device_get(tr2.last_health_handle)
    np.testing.assert_allclose(h1["grad_norm"], h2["grad_norm"],
                               rtol=1e-3)
    for layer, st in h1["act"].items():
        for k, v in st.items():
            np.testing.assert_allclose(
                np.float64(h2["act"][layer][k]), np.float64(v),
                rtol=1e-3, atol=1e-6)


def test_health_config_namespace():
    hc = parse_health_config([("health", "1"),
                              ("health_interval", "4"),
                              ("health_dead_frac", "0.5")])
    assert (hc.enabled, hc.interval, hc.dead_frac) == (1, 4, 0.5)
    with pytest.raises(ConfigError, match="unknown health setting"):
        parse_health_config([("health_intreval", "4")])
    with pytest.raises(ConfigError, match="health_window"):
        parse_health_config([("health_window", "0")])
    with pytest.raises(ConfigError, match="health_ratio_min"):
        parse_health_config([("health_ratio_min", "1.0"),
                             ("health_ratio_max", "0.5")])


def test_report_renders_model_health_section(tmp_path):
    import importlib
    report = importlib.import_module("tools.report")
    path = str(tmp_path / "ledger.jsonl")
    evs = [
        {"schema": 1, "ts": 1.0, "run_id": "r", "host": 0,
         "event": "run_start", "task": "train"},
        {"schema": 1, "ts": 2.0, "run_id": "r", "host": 0,
         "event": "model_health", "round": 0, "grad_norm": 0.5,
         "dead_max": 0.25, "dead_max_layer": "relu_1"},
        {"schema": 1, "ts": 3.0, "run_id": "r", "host": 0,
         "event": "health_advice", "kind": "bn_collapse",
         "layer": "bn3", "value": 1e-12, "round": 1},
        {"schema": 1, "ts": 4.0, "run_id": "r", "host": 0,
         "event": "rollback", "round": 2, "to_round": 1,
         "reason": "non-finite loss nan [layer=conv3 kind=grad]",
         "provenance": "layer=conv3 kind=grad leaf=wmat",
         "lr_scale": 0.5},
    ]
    with open(path, "w") as f:
        for e in evs:
            f.write(json.dumps(e) + "\n")
    md = report.generate(path, None, [])
    assert "## Model health" in md
    assert "layer=conv3 kind=grad leaf=wmat" in md
    assert "bn_collapse" in md and "bn3" in md
    assert "relu_1" in md
    # the health events stay OUT of the generic incident timeline; the
    # rollback stays in and carries its provenance
    head = md.split("## Model health")[0]
    assert "bn_collapse" not in head
    assert "rollback" in head


def test_ckpt_health_tool(tmp_path):
    import importlib
    ckpt_health = importlib.import_module("tools.ckpt_health")
    from cxxnet_tpu import checkpoint as ckpt
    tr = make_trainer()
    sig = tr.graph.structure_signature()
    params = _gather_np(tr, tr.params)
    a = str(tmp_path / "0001.model")
    b = str(tmp_path / "0002.model")
    ckpt.save_model(a, params=params, net_state={}, opt_state=None,
                    structure_sig=sig, round_counter=1, epoch_counter=0)
    nudged = jax.tree_util.tree_map(lambda x: x * 1.01, params)
    ckpt.save_model(b, params=nudged, net_state={}, opt_state=None,
                    structure_sig=sig, round_counter=2, epoch_counter=0)
    assert ckpt_health.main([a]) == 0
    assert ckpt_health.main([a, b]) == 0          # RELOAD-SANE
    assert ckpt_health.main([a, a]) == 0          # IDENTICAL
    big = jax.tree_util.tree_map(lambda x: x * 10.0, params)
    c = str(tmp_path / "0003.model")
    ckpt.save_model(c, params=big, net_state={}, opt_state=None,
                    structure_sig=sig, round_counter=3, epoch_counter=0)
    assert ckpt_health.main([a, c]) == 1          # RELOAD-SUSPECT
    bad = dict(nudged)
    bad["fc2"] = {k: np.full_like(v, np.nan)
                  for k, v in nudged["fc2"].items()}
    d = str(tmp_path / "0004.model")
    ckpt.save_model(d, params=bad, net_state={}, opt_state=None,
                    structure_sig=sig, round_counter=4, epoch_counter=0)
    assert ckpt_health.main([d]) == 2             # RELOAD-UNSAFE
    # structural mismatch: a model missing a layer
    slim = {"fc1": params["fc1"]}
    e = str(tmp_path / "0005.model")
    ckpt.save_model(e, params=slim, net_state={}, opt_state=None,
                    structure_sig=sig, round_counter=5, epoch_counter=0)
    assert ckpt_health.main([a, e]) == 2
