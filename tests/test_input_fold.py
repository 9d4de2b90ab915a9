"""The in-step uint8 decode-normalize (``input_fold``) and stem channel
padding through the trainer: bit parity with the eager path, the chained
dispatches, the bytes the fold saves, and each option's off value.
(``decode_normalize`` itself against numpy: tests/test_convnet_ops.py.)"""

import numpy as np

from cxxnet_tpu.config import parse_config_string
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.trainer import Trainer

RNG = np.random.RandomState(7)


# -- trainer integration: input_fold + stem_pad -------------------------------

CONF = """
netconfig = start
layer[0->1] = conv:cv1
  kernel_size = 3
  nchannel = 8
  stride = 2
  pad = 1
layer[1->2] = batch_norm:bn1
layer[2->3] = relu
layer[3->4] = max_pooling:mp
  kernel_size = 2
  stride = 2
layer[4->5] = flatten
layer[5->6] = fullc:fc
  nhidden = 5
layer[6->6] = softmax
netconfig = end
input_shape = 3,16,16
batch_size = 8
eval_train = 0
dev = cpu:0-0
"""


def _run(overrides, batch_fn, n=4):
    tr = Trainer(parse_config_string(CONF) + list(overrides))
    tr.init_model()
    out = []
    for _ in range(n):
        tr.update(batch_fn())
        out.append(tr.last_loss)
    return out, tr


U8 = RNG.randint(0, 256, (8, 16, 16, 3), np.uint8)
LAB = RNG.randint(0, 5, (8, 1)).astype(np.float32)
NORM = {"mean": np.asarray([120.0, 110.0, 100.0], np.float32),
        "divideby": 255.0, "scale": 1.0}


def _u8_batch():
    return DataBatch(data=U8.copy(), label=LAB.copy(), norm=dict(NORM))


def test_input_fold_bit_parity_and_hatch():
    """Folded (in-step) normalization is bit-identical to the eager
    _device_normalize path under the fp32 policy; input_fold=0 is the
    escape hatch and must change nothing."""
    l_fold, tr = _run((), _u8_batch)
    l_eager, tr0 = _run((("input_fold", "0"),), _u8_batch)
    assert tr.input_fold and not tr0.input_fold
    np.testing.assert_array_equal(np.asarray(l_fold),
                                  np.asarray(l_eager))


def test_input_fold_chain_paths():
    tr = Trainer(parse_config_string(CONF))
    tr.init_model()
    losses = tr.update_chain(_u8_batch(), 3)
    assert np.all(np.isfinite(np.asarray(losses)))
    losses2 = tr.update_chain_batches([_u8_batch(), _u8_batch()])
    assert np.all(np.isfinite(np.asarray(losses2)))


def test_input_fold_cost_analysis_smaller():
    """The folded step's compiled cost analysis must charge fewer bytes
    than the f32-input step: the uint8 input is 1/4 the read and the
    fp32 normalize round-trip is gone."""
    tr = Trainer(parse_config_string(CONF))
    tr.init_model()
    cost_fold = tr.step_cost_analysis(_u8_batch())
    f32 = ((U8.astype(np.float32) - NORM["mean"]) / 255.0)
    cost_f32 = tr.step_cost_analysis(
        DataBatch(data=f32, label=LAB.copy()))
    assert cost_fold["bytes_accessed"] < cost_f32["bytes_accessed"]


def test_input_fold_eval_unchanged():
    """Eval/predict stages normalize eagerly — a fold-capable batch
    predicts identically with the fold on and off."""
    tr = Trainer(parse_config_string(CONF))
    tr.init_model()
    p1 = tr.predict_raw(_u8_batch())
    tr0 = Trainer(parse_config_string(CONF) + [("input_fold", "0")])
    tr0.init_model()
    p0 = tr0.predict_raw(_u8_batch())
    np.testing.assert_array_equal(p1, p0)


def test_stem_pad_parity_and_hatch():
    f32 = RNG.rand(8, 16, 16, 3).astype(np.float32)
    mk = lambda: DataBatch(data=f32.copy(), label=LAB.copy())
    l_pad, tr = _run((), mk)
    l_off, tr0 = _run((("stem_pad", "0"),), mk)
    assert tr.net._cin_pad == {0: 4} and tr0.net._cin_pad == {}
    np.testing.assert_allclose(np.asarray(l_pad), np.asarray(l_off),
                               rtol=1e-6, atol=1e-7)


def test_stem_pad_checkpoint_shape_unchanged():
    """Padding is apply-time only: params keep the canonical cin."""
    tr = Trainer(parse_config_string(CONF))
    tr.init_model()
    assert tr.params["cv1"]["wmat"].shape == (3, 3, 3, 8)
