"""benchmarks/reference.py against the program, on the CPU at toy
widths, for every layer kind of the benchmark's two nets — so that the
reference is not first run on the chip — and flops.py against hand
counts.

Tolerance: both sides compute in float32 here, in different orders (the
program through its fused kernels, interpreted, with one-pass batch
moments; the reference in two passes), so they agree to a few float32
roundings of a loss of ~2.3: 1e-5. A reference that skipped a layer, or
read a weight in the wrong layout, misses by 1e-2 and more (checked
below by breaking it on purpose).
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import importlib.util  # noqa: E402
import types  # noqa: E402

from benchmarks import flops, reference  # noqa: E402
from cxxnet_tpu.config import parse_config_string  # noqa: E402
from cxxnet_tpu.io.data import DataBatch  # noqa: E402
from cxxnet_tpu.trainer import Trainer  # noqa: E402

TOL = 1e-5


def convnet_module():
    """``benchmarks/references/convnet.py``, loaded as ``run.py`` loads it."""
    spec = importlib.util.spec_from_file_location(
        "bench_convnet", os.path.join(ROOT, "benchmarks", "references",
                                      "convnet.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

#: the flagship's kinds: conv, batch_norm, relu, max and avg pooling
#: (overlapping, padded, ceil-mode, global), split, ch_concat, flatten,
#: fullc, softmax
INCEPTION_LIKE = """
netconfig = start
layer[in->c1] = conv:cv1
  kernel_size = 3
  nchannel = 8
  stride = 2
  pad = 1
  no_bias = 1
layer[c1->b1] = batch_norm:bn1
layer[b1->a1] = relu:ac1
layer[a1->p1] = max_pooling:mp1
  kernel_size = 3
  stride = 2
layer[p1->s1,s2,s3] = split:sp
layer[s1->d1] = conv:cv2
  kernel_size = 1
  nchannel = 4
  no_bias = 1
layer[d1->d2] = batch_norm:bn2
layer[d2->d3] = relu:ac2
layer[s2->e1] = avg_pooling:ap1
  kernel_size = 3
  stride = 1
  pad = 1
layer[s3->f1] = max_pooling:mp2
  kernel_size = 3
  stride = 1
  pad = 1
layer[d3,e1,f1->cc] = ch_concat:cc1
layer[cc->gap] = avg_pooling:gap
  kernel_size = 4
  stride = 1
layer[gap->fl] = flatten:fl
layer[fl->fc] = fullc:fc1
  nhidden = 7
layer[fc->fc] = softmax:loss
netconfig = end
input_shape = 3,18,18
batch_size = 6
metric = error
"""

#: AlexNet's kinds: strided and grouped conv with bias, relu, max
#: pooling, lrn, flatten, fullc, dropout, softmax
ALEXNET_LIKE = """
netconfig = start
layer[0->1] = conv:cv1
  kernel_size = 5
  stride = 2
  nchannel = 8
layer[1->2] = relu:ac1
layer[2->3] = max_pooling:mp1
  kernel_size = 3
  stride = 2
layer[3->4] = lrn:lrn1
  local_size = 5
  alpha = 0.0001
  beta = 0.75
  knorm = 1
layer[4->5] = conv:cv2
  ngroup = 2
  nchannel = 12
  kernel_size = 3
  pad = 1
  init_bias = 1.0
layer[5->6] = relu:ac2
layer[6->7] = flatten:fl
layer[7->8] = fullc:fc6
  nhidden = 16
layer[8->9] = relu:ac6
layer[9->9] = dropout:dp6
  threshold = 0.5
layer[9->10] = fullc:fc8
  nhidden = 7
layer[10->10] = softmax
netconfig = end
input_shape = 3,23,23
batch_size = 6
metric = error
"""


def build(text, fused):
    tr = Trainer(parse_config_string(
        text + f"dev = cpu:0\nseed = 5\nfused_kernels = {fused}\n"))
    tr.init_model()
    c, y, x = tr.graph.input_shape
    rng = np.random.RandomState(11)
    batch = DataBatch(
        data=rng.randn(6, y, x, c).astype(np.float32),
        label=rng.randint(0, 7, (6, 1)).astype(np.float32))
    return tr, batch


@pytest.mark.parametrize("fused", [0, 1])
def test_train_loss_matches_the_program_inception_kinds(fused):
    import jax
    tr, batch = build(INCEPTION_LIKE, fused)
    layers, defaults = tr.graph.layers, dict(tr.graph.defcfg)
    assert {s.type for s in layers} == {
        "conv", "batch_norm", "relu", "max_pooling", "avg_pooling",
        "split", "ch_concat", "flatten", "fullc", "softmax"}
    loss = jax.jit(reference.make_loss_fn(layers, defaults))
    want = float(loss(tr.params, batch.data, batch.label))
    params0 = jax.tree_util.tree_map(np.array, tr.params)
    tr.update(batch)
    assert abs(tr.last_loss - want) < TOL
    # the check has teeth: a weight read in the wrong order moves the
    # reference by far more than the tolerance
    broken = dict(params0)
    broken["fc1"] = {"wmat": np.asarray(params0["fc1"]["wmat"])[::-1],
                     "bias": params0["fc1"]["bias"]}
    assert abs(float(loss(broken, batch.data, batch.label)) - want) > 1e-3
    # eval mode reads the running statistics, not the batch's
    probs = jax.jit(reference.make_eval_fn(layers, defaults))(
        tr.params, tr.net_state, batch.data)
    got = tr.predict_raw(batch)
    assert np.max(np.abs(reference.centered_log(probs)
                         - reference.centered_log(got))) < 1e-3


@pytest.mark.parametrize("fused", [0, 1])
def test_eval_logits_match_the_program_alexnet_kinds(fused):
    import jax
    tr, batch = build(ALEXNET_LIKE, fused)
    layers, defaults = tr.graph.layers, dict(tr.graph.defcfg)
    assert {s.type for s in layers} == {
        "conv", "relu", "max_pooling", "lrn", "flatten", "fullc",
        "dropout", "softmax"}
    fn = jax.jit(reference.make_eval_fn(layers, defaults))
    want = reference.centered_log(fn(tr.params, tr.net_state, batch.data))
    got = reference.centered_log(tr.predict_raw(batch))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-4
    # teeth: a wrong LRN constant shows at once
    wrong = [(k, "2" if k == "knorm" else v) for k, v in layers[3].cfg]
    import dataclasses
    bad_layers = list(layers)
    bad_layers[3] = dataclasses.replace(layers[3], cfg=wrong)
    bad = reference.centered_log(jax.jit(reference.make_eval_fn(
        bad_layers, defaults))(tr.params, tr.net_state, batch.data))
    assert np.max(np.abs(bad - want)) / np.max(np.abs(want)) > 1e-4
    # with dropout off the train-mode loss is the program's too
    text = ALEXNET_LIKE.replace("threshold = 0.5", "threshold = 0.0")
    tr2, _ = build(text, fused)
    want_loss = float(jax.jit(reference.make_loss_fn(
        tr2.graph.layers, dict(tr2.graph.defcfg)))(
            tr2.params, batch.data, batch.label))
    tr2.update(batch)
    assert abs(tr2.last_loss - want_loss) < TOL


@pytest.mark.parametrize("kind", ["train_loss", "eval_logits"])
def test_the_convnet_module_says_what_run_py_said(kind):
    """The two checks moved from ``run.py`` into ``references/convnet.py``
    (PR 27): through the module's ``check`` each gives, key for key and
    number for number, the dictionary ``run.py``'s own function gave —
    written out here as it stood there."""
    import jax
    convnet = convnet_module()
    assert convnet.LOSS_TOL == {"bfloat16": 5e-3, "float32": 1e-3}
    assert convnet.LOGIT_TOL == {"bfloat16": 5e-2, "float32": 1e-3}
    assert convnet.needs_initial_params("train_loss")
    assert not convnet.needs_initial_params("eval_logits")
    tr, batch = build(INCEPTION_LIKE if kind == "train_loss"
                      else ALEXNET_LIKE, 0)
    layers, defaults = tr.graph.layers, dict(tr.graph.defcfg)
    params0 = jax.tree_util.tree_map(np.array, tr.params)
    tr.update(batch)
    view = {"layers": layers, "defaults": defaults, "trainer": tr,
            "params0": params0, "batch0": batch,
            "warm_losses": [tr.last_loss, 0.0], "dtype": "float32",
            "rows": 6, "chips": 1, "config": {}, "say": print}
    ok, said = convnet.check(kind, view)
    if kind == "train_loss":
        want = float(jax.jit(reference.make_loss_fn(layers, defaults))(
            params0, reference.normalise(batch.data, batch.norm),
            np.asarray(batch.label)))
        before = {"check": "train_loss", "program": tr.last_loss,
                  "reference": want, "abs_diff": abs(tr.last_loss - want),
                  "tolerance": 1e-3}
        assert said["abs_diff"] < TOL
    else:
        got = reference.centered_log(tr.predict_raw(batch))
        ref = reference.centered_log(np.asarray(jax.jit(
            reference.make_eval_fn(layers, defaults))(
                tr.params, tr.net_state,
                reference.normalise(batch.data, batch.norm))))
        err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        before = {"check": "eval_logits", "rel_err": err, "tolerance": 1e-3,
                  "max_abs_logit": float(np.max(np.abs(ref)))}
        assert said["rel_err"] < 1e-4
    assert ok is True
    assert list(said) == list(before) and said == before
    with pytest.raises(ValueError, match="no check 'perplexity'"):
        convnet.check("perplexity", view)


def test_unknown_layer_kind_is_an_error():
    tr, batch = build(INCEPTION_LIKE.replace("relu:ac2", "sigmoid:ac2"), 0)
    with pytest.raises(ValueError, match="no layer kind 'sigmoid'"):
        reference.forward(tr.graph.layers, {}, tr.params, {}, batch.data,
                          True)


def test_normalise_is_the_device_path_arithmetic():
    data = np.arange(24, dtype=np.uint8).reshape(1, 2, 4, 3)
    mean = np.full((2, 4, 3), 2.0, np.float32)
    out = reference.normalise(data, {"mean": mean, "divideby": 4.0})
    assert np.allclose(out, (data.astype(np.float32) - 2.0) / 4.0)
    assert np.allclose(reference.normalise(data, None), data)


def test_pool_out_is_ceil_mode():
    assert reference.pool_out(112, 3, 2, 0) == 56      # the flagship's stem
    assert reference.pool_out(55, 3, 2, 0) == 27       # AlexNet's mp1
    assert reference.pool_out(28, 3, 1, 1) == 28
    assert reference.pool_out(7, 7, 1, 0) == 1


# -- flops.py against hand counts -----------------------------------------


def test_flops_hand_counts():
    # one conv: 2 rows, 5x5 output, 3x3 window over 4 channels, 6 filters
    # -> 2*5*5*6 outputs x (3*3*4) multiply-adds x 2
    assert flops.conv_flops(2, 5, 5, 3, 3, 4, 6) == 2 * 5 * 5 * 6 * 36 * 2
    # grouped: each filter sees only its group's 4/2 channels
    assert flops.conv_flops(2, 5, 5, 3, 3, 4, 6, groups=2) \
        == 2 * 5 * 5 * 6 * 18 * 2
    # fullc: 2 rows x 10 inputs x 7 outputs multiply-adds x 2
    assert flops.fullc_flops(2, 10, 7) == 280
    records = [
        ("conv", "c", (2, 7, 7, 4), (2, 5, 5, 6),
         {"kernel_size": "3", "ngroup": "2"}, True),
        ("relu", "r", (2, 5, 5, 6), (2, 5, 5, 6), {}, False),
        ("fullc", "f", (2, 5, 5, 6), (2, 1, 1, 7), {}, False),
    ]
    conv, fc = 2 * 5 * 5 * 6 * 18 * 2, 2 * 150 * 7 * 2
    assert flops.forward_flops(records) == conv + fc
    # the conv reads the images: forward and dW only
    assert flops.train_step_flops(records) == 2 * conv + 3 * fc


def graph_and_shapes(name):
    """A benchmark conf's graph and the shapes of its weights."""
    from cxxnet_tpu.graph import build_graph
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".conf")) as f:
        graph = build_graph(parse_config_string(f.read()))
    return graph, _param_shapes(graph, graph.input_shape)


def step_count_through_the_module(name, rows):
    """``references/convnet.py:train_step_flops`` on the view ``run.py``
    hands it, for a benchmark conf at ``rows`` rows: shapes only."""
    graph, shapes = graph_and_shapes(name)
    c, y, x = graph.input_shape
    view = {"layers": graph.layers, "defaults": dict(graph.defcfg),
            "trainer": types.SimpleNamespace(params=shapes),
            "batch0": types.SimpleNamespace(
                data=np.zeros((1, y, x, c), np.uint8)),
            "rows": rows, "chips": 1, "config": {}}
    return convnet_module().train_step_flops(view)


@pytest.mark.parametrize("name,lo,hi,gflop_an_image", [
    ("alexnet", 0.65e9, 0.80e9, 4.1356),
    ("inception_bn", 1.8e9, 2.2e9, 11.960)])
def test_flops_of_the_two_nets_from_their_shapes(name, lo, hi,
                                                 gflop_an_image):
    """The forward counts the literature gives: AlexNet ~0.72 G
    multiply-adds per 227 image, BN-Inception ~2.0 G per 224 image. And
    the step's count the way ``run.py`` asks for it since PR 27, through
    the reference module: what every ``step_mfu_pct`` on record divides
    by, to its five digits."""
    import jax
    graph, shapes = graph_and_shapes(name)
    c, y, x = graph.input_shape
    records = []
    jax.eval_shape(lambda p, d: reference.forward(
        graph.layers, dict(graph.defcfg), p, {}, d, True,
        record=records), shapes,
        jax.ShapeDtypeStruct((1, y, x, c), np.float32))
    macs = flops.forward_flops(records) / 2
    assert lo < macs < hi, (name, macs)
    step = step_count_through_the_module(name, 8)
    assert step == 8 * flops.train_step_flops(records)
    assert float(f"{step / 8 / 1e9:.5g}") == gflop_an_image


def test_the_counts_on_record_through_the_reference_module():
    """What ``run.py`` counted on the chip for 256 rows of the flagship
    (PR 25: the traced runs' value x period x peak gives it back to the
    last digit, and did again on both sides in PR 27) is what
    ``references/convnet.py`` counts: the same code in another file. And
    the constants PERF.md divides by: % of a v5e per item/s/chip."""
    flagship = step_count_through_the_module("inception_bn", 256)
    assert flagship == 3061650554880.0
    alexnet = step_count_through_the_module("alexnet", 1024)
    assert alexnet == 4234865147904.0
    peak = flops.chip_peaks("TPU v5 lite")["bf16_tflops"] * 1e12
    assert 100 * flagship / 256 / peak == pytest.approx(6.0709e-3, rel=1e-4)
    assert 100 * alexnet / 1024 / peak == pytest.approx(2.0993e-3, rel=1e-4)


def _param_shapes(graph, in_shape):
    """Weight shapes of a conf's conv / batch_norm / fullc layers, by
    walking the shapes as reference.forward does."""
    import jax
    c, y, x = in_shape
    node = {0: (y, x, c)}
    out = {}
    for spec in graph.layers:
        hp = dict(spec.cfg)
        h, w, ch = node[spec.nindex_in[0]]
        k = int(hp.get("kernel_size", 0))
        s, p = int(hp.get("stride", 1)), int(hp.get("pad", 0))
        sd = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32)
        res = (h, w, ch)
        if spec.type == "conv":
            n, g = int(hp["nchannel"]), int(hp.get("ngroup", 1))
            out[spec.name] = {"wmat": sd(k, k, ch // g, n)}
            if not int(hp.get("no_bias", 0)):
                out[spec.name]["bias"] = sd(n)
            res = ((h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1, n)
        elif spec.type == "batch_norm":
            out[spec.name] = {"wmat": sd(ch), "bias": sd(ch)}
        elif spec.type in ("max_pooling", "avg_pooling"):
            res = (reference.pool_out(h, k, s, p),
                   reference.pool_out(w, k, s, p), ch)
        elif spec.type == "ch_concat":
            res = (h, w, sum(node[i][2] for i in spec.nindex_in))
        elif spec.type == "flatten":
            res = (1, 1, h * w * ch)
        elif spec.type == "fullc":
            n = int(hp["nhidden"])
            out[spec.name] = {"wmat": sd(h * w * ch, n), "bias": sd(n)}
            res = (1, 1, n)
        for i in spec.nindex_out:
            node[i] = res
    return out
