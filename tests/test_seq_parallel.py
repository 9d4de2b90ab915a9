"""Config-driven sequence parallelism: seq_parallel=k runs the whole train
step under shard_map with ring attention inside; losses, gradients, and
training trajectories must match the single-shard (GSPMD) path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.config import parse_config_string
from cxxnet_tpu.io.data import create_iterator
from cxxnet_tpu.parallel import make_mesh_context
from cxxnet_tpu.trainer import Trainer

V, S = 16, 32

LM_CFG = f"""
netconfig=start
layer[+1:e0] = embed:tok_embed
  nhidden = 32
  vocab_size = {V}
  random_type = gaussian
  init_sigma = 0.02
layer[+1:n1] = layernorm:ln1
layer[+1:a1] = mha:attn1
  nhead = 4
  causal = 1
  rope = 1
layer[e0,a1->r1] = add:res1
layer[+1:n2] = layernorm:ln2
layer[+1:f1] = ffn:ffn1
  nhidden = 64
layer[r1,f1->r2] = add:res2
layer[+1:nf] = layernorm:lnf
layer[+1:lg] = seqfc:lm_head
  nhidden = {V}
layer[+0] = lmloss
netconfig=end
input_shape = 1,1,{S}
label_vec[0,{S}) = label
batch_size = 16
updater = adam
eta = 0.01
metric = seq_error
seed = 3
"""

ITER_CFG = f"""
iter = synthetic_lm
num_inst = 128
batch_size = 16
vocab_size = {V}
seq_len = {S}
seed_data = 4
lm_task = copy
"""


def _trainer(sp):
    ctx = make_mesh_context(devices=jax.devices(), seq_parallel=sp)
    tr = Trainer(parse_config_string(LM_CFG), mesh_ctx=ctx)
    tr.init_model()
    return tr


def test_sp_step_matches_gspmd_step():
    tr1 = _trainer(1)
    tr4 = _trainer(4)          # dp=2 x sp=4 on the 8-device mesh
    it = create_iterator(parse_config_string(ITER_CFG))
    b = next(iter(it))
    tr1.update(b)
    tr4.update(b)
    # same init seed -> same params; one step must agree closely
    np.testing.assert_allclose(float(tr1.last_loss), float(tr4.last_loss),
                               rtol=1e-5)
    w1 = tr1.get_weight("attn1", "q.wmat")
    w4 = tr4.get_weight("attn1", "q.wmat")
    np.testing.assert_allclose(w1, w4, atol=1e-5)


def test_sp_trains_and_evaluates():
    tr = _trainer(4)
    it = create_iterator(parse_config_string(ITER_CFG))
    first = None
    for r in range(6):
        for b in it:
            tr.update(b)
            first = first or tr.last_loss
    assert tr.last_loss < 0.7 * first
    s = tr.evaluate(iter(create_iterator(parse_config_string(ITER_CFG))),
                    "eval")
    err = float(s.split(":")[-1])
    assert err < 0.6
    # train metrics ride the sp top node too
    rep = tr.train_metric_report("train")
    assert "train-seq_error" in rep


def test_sp_rejects_unshardable_graphs():
    conv_cfg = """
netconfig=start
layer[+1] = conv
  kernel_size = 3
  nchannel = 4
layer[+1] = flatten
layer[+1] = fullc
  nhidden = 4
layer[+0] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 16
"""
    ctx = make_mesh_context(devices=jax.devices(), seq_parallel=4)
    with pytest.raises(ValueError, match="not\\s+sequence-shardable"):
        Trainer(parse_config_string(conv_cfg), mesh_ctx=ctx)


def test_sp_posembed_matches_sp1():
    """posembed under seq_parallel: the replicated table is offset-indexed
    per shard (global positions), so absolute position embeddings match
    the unsharded run exactly — rope is no longer the only option."""
    cfg = LM_CFG.replace("  rope = 1\n", "").replace(
        "layer[+1:n1] = layernorm:ln1",
        "layer[+1:pe] = posembed:pos\nlayer[+1:n1] = layernorm:ln1")
    it = create_iterator(parse_config_string(ITER_CFG))
    b = next(iter(it))
    losses = {}
    for sp in (1, 4):
        ctx = make_mesh_context(devices=jax.devices(), seq_parallel=sp)
        tr = Trainer(parse_config_string(cfg), mesh_ctx=ctx)
        tr.init_model()
        tr.update(b)
        losses[sp] = float(tr.last_loss)
        pe = tr.get_weight("pos", "wmat")
        assert pe.shape == (S, 32)
    assert abs(losses[1] - losses[4]) < 1e-5, losses


def test_sp_with_moe_state():
    """Regression: layer state computed from local shards (MoE aux loss)
    must leave the shard_map replicated, not shard-varying."""
    cfg = LM_CFG.replace(
        "layer[+1:f1] = ffn:ffn1\n  nhidden = 64",
        "layer[+1:f1] = moe:moe1\n  num_expert = 4\n  topk = 2\n"
        "  nhidden = 64")
    ctx = make_mesh_context(devices=jax.devices(), seq_parallel=4)
    tr = Trainer(parse_config_string(cfg), mesh_ctx=ctx)
    tr.init_model()
    it = create_iterator(parse_config_string(ITER_CFG))
    b = next(iter(it))
    tr.update(b)
    tr.update(b)
    aux = float(tr.net_state["moe1"]["_aux_loss"])
    assert np.isfinite(tr.last_loss) and 0.0 < aux < 0.2


def test_sp_composes_with_tp():
    """seq_parallel x model_parallel: the partial-manual shard_map leaves
    the 'model' axis to GSPMD, so TP param shardings (mha heads, MoE
    experts) keep working inside the sp step — losses match the
    single-device run."""
    it = create_iterator(parse_config_string(ITER_CFG))
    b = next(iter(it))
    ctx = make_mesh_context(devices=jax.devices(), seq_parallel=2,
                            model_parallel=2)
    tr = Trainer(parse_config_string(LM_CFG), mesh_ctx=ctx)
    tr.init_model()
    tr.update(b)
    tr.update(b)
    ref = Trainer(parse_config_string(LM_CFG),
                  mesh_ctx=make_mesh_context(devices=jax.devices()[:1]))
    ref.init_model()
    ref.update(b)
    ref.update(b)
    assert abs(float(tr.last_loss) - float(ref.last_loss)) < 1e-4
    # eval path too
    e_sp = float(tr.evaluate(it, "e").split(":")[-1])
    e_ref = float(ref.evaluate(it, "e").split(":")[-1])
    assert abs(e_sp - e_ref) < 1e-6


def test_sp_nontop_metrics_and_extract():
    """Metrics bound to non-top nodes and extract_feature now work under
    seq_parallel (previously guarded off)."""
    cfg = LM_CFG + "metric[label,r2] = seq_error\n"
    ctx = make_mesh_context(devices=jax.devices(), seq_parallel=4)
    tr = Trainer(parse_config_string(cfg), mesh_ctx=ctx)
    tr.init_model()
    it = create_iterator(parse_config_string(ITER_CFG))
    b = next(iter(it))
    # extracted values (same fresh init) match the unsharded model's
    feats = tr.extract_feature(b, "r2")
    assert feats.shape == (16, S * 32)
    ref = Trainer(parse_config_string(cfg),
                  mesh_ctx=make_mesh_context(devices=jax.devices()[:1]))
    ref.init_model()
    np.testing.assert_allclose(feats, ref.extract_feature(b, "r2"),
                               rtol=2e-4, atol=2e-5)
    # training + eval with the non-top-bound metric work
    tr.update(b)
    out = tr.evaluate(it, "ev")
    assert out.count("seq_error") == 2       # top metric + r2-bound metric


def test_sp_moe_global_routing_matches_sp1():
    """MoE routing under seq_parallel is GLOBAL (capacity from the global
    token count, cross-shard position offsets): with a deliberately tight
    capacity that forces token drops, the sp=4 loss must match sp=1
    exactly — shard-local routing would drop different tokens."""
    cfg = LM_CFG.replace(
        "layer[+1:f1] = ffn:ffn1\n  nhidden = 64",
        "layer[+1:f1] = moe:moe1\n  num_expert = 4\n  topk = 1\n"
        "  capacity_factor = 0.5\n  nhidden = 64")
    it = create_iterator(parse_config_string(ITER_CFG))
    b = next(iter(it))
    losses = {}
    for sp in (1, 4):
        ctx = make_mesh_context(devices=jax.devices(), seq_parallel=sp)
        tr = Trainer(parse_config_string(cfg), mesh_ctx=ctx)
        tr.init_model()
        tr.update(b)
        losses[sp] = float(tr.last_loss)
    assert abs(losses[1] - losses[4]) < 1e-4, losses


def test_sp_multi_slice_labels_match_sp1():
    """Multiple label_vec slices under seq_parallel: labels are pre-sliced
    per range on the host and each slice sharded token-aligned, so two
    loss heads with different slices train identically to sp=1."""
    from cxxnet_tpu.io.data import DataBatch
    cfg = LM_CFG.replace(f"label_vec[0,{S}) = label",
                         f"label_vec[0,{S}) = la\nlabel_vec[{S},{2*S}) = lb")
    # the stock metric binds label_field "label", which no longer exists
    cfg = cfg.replace("metric = seq_error", "eval_train = 0")
    cfg = cfg.replace(
        "layer[+1:lg] = seqfc:lm_head\n  nhidden = {V}".replace("{V}",
                                                                str(V)),
        f"layer[nf->lg] = seqfc:lm_head\n  nhidden = {V}\n"
        f"layer[nf->lg2] = seqfc:aux_head\n  nhidden = {V}")
    cfg = cfg.replace(
        "layer[+0] = lmloss",
        "layer[lg->lg] = lmloss\n  target = la\n"
        "layer[lg2->lg2] = lmloss\n  target = lb\n  grad_scale = 0.5")
    rng = np.random.RandomState(0)
    toks = rng.randint(0, V, (16, S))
    b = DataBatch(
        data=toks.reshape(16, 1, 1, S).astype(np.float32),
        label=np.concatenate([np.roll(toks, -1, axis=1),
                              toks], axis=1).astype(np.float32))
    losses = {}
    for sp in (1, 4):
        ctx = make_mesh_context(devices=jax.devices(), seq_parallel=sp)
        tr = Trainer(parse_config_string(cfg), mesh_ctx=ctx)
        tr.init_model()
        tr.update(b)
        tr.update(b)
        losses[sp] = float(tr.last_loss)
    assert abs(losses[1] - losses[4]) < 1e-5, losses
    # a slice whose width the seq axis cannot divide still fails fast
    bad = cfg.replace(f"label_vec[{S},{2*S}) = lb",
                      f"label_vec[{S},{S+3}) = lb")
    ctx = make_mesh_context(devices=jax.devices(), seq_parallel=4)
    with pytest.raises(ValueError, match="not divisible"):
        Trainer(parse_config_string(bad), mesh_ctx=ctx)


def test_sp_moe_expert_capacity_sharded():
    """The sp expert FFN is capacity-sharded: each seq shard computes only
    C/sp capacity slots (reduce-scatter in, all-gather out) instead of
    replicating the whole expert batch. Checks (a) the lowered sp step
    really contains a reduce-scatter, (b) a capacity NOT divisible by sp
    (zero-padded slots) still matches sp=1 exactly under forced drops."""
    cfg = LM_CFG.replace(
        "layer[+1:f1] = ffn:ffn1\n  nhidden = 64",
        "layer[+1:f1] = moe:moe1\n  num_expert = 4\n  topk = 1\n"
        "  capacity_factor = 0.75\n  nhidden = 64")   # C=6, sp=4 -> pad 2
    it = create_iterator(parse_config_string(ITER_CFG))
    b = next(iter(it))
    losses = {}
    for sp in (1, 4):
        ctx = make_mesh_context(devices=jax.devices(), seq_parallel=sp)
        tr = Trainer(parse_config_string(cfg), mesh_ctx=ctx)
        tr.init_model()
        tr.update(b)
        losses[sp] = float(tr.last_loss)
    assert abs(losses[1] - losses[4]) < 1e-4, losses
    # structural: the sp train step lowers with a reduce-scatter (the
    # capacity shard handoff), not just the psum a replicated FFN would use
    step = tr._train_step_fns[(True, "sp", None)]
    data, label = tr._shard_seq_batch(b.data, b.label)
    txt = step.lower(tr.params, tr.opt_state, tr.net_state, {}, data,
                     label, tr._mask(b), jax.random.PRNGKey(0),
                     tr._sched_scalars()).as_text()
    assert "reduce_scatter" in txt or "reduce-scatter" in txt


def test_sp_update_chain_matches_sequential_updates():
    """update_chain under seq_parallel: k steps scanned inside the sp
    shard_map (one dispatch) must reproduce k sequential update() calls
    — same rng chain, schedules held (constant here)."""
    tr_c = _trainer(4)
    tr_s = _trainer(4)
    it = create_iterator(parse_config_string(ITER_CFG))
    b = next(iter(it))
    losses = np.asarray(tr_c.update_chain(b, 3))
    seq = []
    for _ in range(3):
        tr_s.update(b)
        seq.append(float(tr_s.last_loss))
    np.testing.assert_allclose(losses, seq, rtol=1e-5)
    np.testing.assert_allclose(tr_c.get_weight("attn1", "q.wmat"),
                               tr_s.get_weight("attn1", "q.wmat"),
                               rtol=1e-5, atol=1e-6)


def test_sp_update_chain_batches_matches_sequential():
    """DISTINCT stacked batches under sp (train_chain's staging): one
    fused dispatch must reproduce sequential update() calls — and the
    train-metric line must survive the chain (per-step node banking)."""
    tr_c = _trainer(4)
    tr_s = _trainer(4)
    it = create_iterator(parse_config_string(ITER_CFG))
    batches = [b for b, _ in zip(iter(it), range(3))]
    losses = np.asarray(tr_c.update_chain_batches(batches))
    seq = []
    for b in batches:
        tr_s.update(b)
        seq.append(float(tr_s.last_loss))
    np.testing.assert_allclose(losses, seq, rtol=1e-5)
    np.testing.assert_allclose(tr_c.get_weight("attn1", "q.wmat"),
                               tr_s.get_weight("attn1", "q.wmat"),
                               rtol=1e-5, atol=1e-6)
    rep_c = tr_c.train_metric_report("train")
    rep_s = tr_s.train_metric_report("train")
    assert "train-seq_error" in rep_c
    assert rep_c == rep_s


def test_sp_update_chain_batches_applies_deferred_norm():
    """The sp chain branch must honor deferred-norm metadata exactly as
    regular sp update() does (advisor r4 medium): batches shipped as
    2x-scaled values with divideby=2 must train identically to the
    plain batches."""
    from cxxnet_tpu.io.data import DataBatch
    tr_c = _trainer(4)
    tr_s = _trainer(4)
    it = create_iterator(parse_config_string(ITER_CFG))
    batches = [b for b, _ in zip(iter(it), range(2))]
    normed = [DataBatch(data=np.asarray(b.data, np.float32) * 2.0,
                        label=np.asarray(b.label),
                        num_batch_padd=b.num_batch_padd,
                        norm={"divideby": 2.0})
              for b in batches]
    losses = np.asarray(tr_c.update_chain_batches(normed))
    seq = []
    for b in batches:
        tr_s.update(b)
        seq.append(float(tr_s.last_loss))
    np.testing.assert_allclose(losses, seq, rtol=1e-5)


def test_sp_update_chain_accepts_prestaged_batch():
    """bench.py holds device-resident batches staged mode-unaware
    (mesh.shard_batch on data AND label); stage_batch must restage the
    label into the sp per-range tuple form instead of tripping the
    chain shard_map's pytree specs."""
    from cxxnet_tpu.io.data import DataBatch
    tr_c = _trainer(4)
    tr_h = _trainer(4)
    it = create_iterator(parse_config_string(ITER_CFG))
    b = next(iter(it))
    staged = DataBatch(data=tr_c.mesh.shard_batch(np.asarray(b.data)),
                       label=tr_c.mesh.shard_batch(np.asarray(b.label)))
    l_dev = np.asarray(tr_c.update_chain(staged, 2))
    l_host = np.asarray(tr_h.update_chain(b, 2))
    np.testing.assert_allclose(l_dev, l_host, rtol=1e-5)
