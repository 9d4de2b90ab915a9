"""Tool tests: weight importer (caffe-converter analog) from npz and torch
state dicts; test_io pipeline benchmark mode; multihost metric reduction."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

MLP_CONF = """
netconfig=start
layer[+1:h1] = fullc:fc1
  nhidden = 8
  random_type = xavier
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 3
  random_type = xavier
layer[+0] = softmax
netconfig=end
input_shape = 1,1,6
batch_size = 8
eta = 0.1
"""


@pytest.fixture
def conf_path(tmp_path):
    p = tmp_path / "net.conf"
    p.write_text(MLP_CONF)
    return str(p)


def test_import_npz(conf_path, tmp_path):
    from import_weights import import_weights
    w1 = np.random.RandomState(0).randn(6, 8).astype(np.float32)
    b1 = np.zeros(8, np.float32)
    npz = tmp_path / "w.npz"
    np.savez(npz, **{"fc1.wmat": w1, "fc1.bias": b1,
                     "unknown.wmat": np.zeros((2, 2), np.float32)})
    out = tmp_path / "out.model"
    n = import_weights(conf_path, str(npz), str(out), verbose=False)
    assert n == 2
    # reload and check the weights landed
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer(parse_config_string(MLP_CONF + "dev = cpu\n"))
    tr.init_model()
    tr.load_model(str(out))
    np.testing.assert_allclose(tr.get_weight("fc1", "wmat"), w1)


def test_import_npz_strict_rejects_unknown(conf_path, tmp_path):
    from import_weights import import_weights
    npz = tmp_path / "w.npz"
    np.savez(npz, **{"nope.wmat": np.zeros((2, 2), np.float32)})
    with pytest.raises(KeyError):
        import_weights(conf_path, str(npz), str(tmp_path / "o.model"),
                       strict=True, verbose=False)


def test_import_torch_state_dict(conf_path, tmp_path):
    torch = pytest.importorskip("torch")
    sd = {"fc1.weight": torch.randn(8, 6),        # Linear (out,in)
          "fc1.bias": torch.zeros(8),
          "fc2.weight": torch.randn(3, 8),
          "fc2.bias": torch.zeros(3)}
    pt = tmp_path / "m.pt"
    torch.save(sd, str(pt))
    from import_weights import import_weights
    out = tmp_path / "out.model"
    n = import_weights(conf_path, str(pt), str(out), verbose=False)
    assert n == 4
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer(parse_config_string(MLP_CONF + "dev = cpu\n"))
    tr.init_model()
    tr.load_model(str(out))
    np.testing.assert_allclose(tr.get_weight("fc1", "wmat"),
                               sd["fc1.weight"].numpy().T, atol=1e-6)


def test_import_rename_map(conf_path, tmp_path):
    from import_weights import import_weights
    npz = tmp_path / "w.npz"
    np.savez(npz, **{"source_fc.wmat":
                     np.ones((6, 8), np.float32)})
    out = tmp_path / "out.model"
    n = import_weights(conf_path, str(npz), str(out),
                       rename={"source_fc": "fc1"}, verbose=False)
    assert n == 1


# ---- caffe importer --------------------------------------------------------

def _vint(n):
    out = b""
    while True:
        b7 = n & 0x7F
        n >>= 7
        if n:
            out += bytes([b7 | 0x80])
        else:
            return out + bytes([b7])


def _ld(field, payload):
    return _vint((field << 3) | 2) + _vint(len(payload)) + payload


def _varint_field(field, val):
    return _vint(field << 3) + _vint(val)


def _blob(arr, legacy=False):
    arr = np.asarray(arr, np.float32)
    msg = b""
    if legacy:
        dims = list(arr.shape) + [1] * (4 - arr.ndim)
        for i, d in enumerate(dims):
            msg += _varint_field(i + 1, d)
    else:
        shape_msg = _ld(1, b"".join(_vint(d) for d in arr.shape))
        msg += _ld(7, shape_msg)
    msg += _ld(5, arr.tobytes())                 # packed float data
    return msg


def _caffe_layer_new(name, ltype, blobs):
    msg = _ld(1, name.encode()) + _ld(2, ltype.encode())
    for b in blobs:
        msg += _ld(7, _blob(b))
    return _ld(100, msg)


def _caffe_layer_v1(name, tcode, blobs):
    msg = _ld(4, name.encode()) + _varint_field(5, tcode)
    for b in blobs:
        msg += _ld(6, _blob(b, legacy=True))
    return _ld(2, msg)


CONV_CONF = """
netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 3
  nchannel = 4
  pad = 1
layer[+1:b] = batch_norm:bn1
layer[+1] = relu
layer[+1] = flatten:fl
layer[+1] = fullc:ip1
  nhidden = 3
layer[+0] = softmax
netconfig=end
input_shape = 3,6,6
batch_size = 8
eta = 0.1
"""


def test_import_caffemodel(tmp_path):
    """Synthetic .caffemodel (hand-encoded NetParameter wire format) lands
    in same-named layers: conv OIHW->HWIO with first-conv BGR->RGB flip,
    InnerProduct transposed, BatchNorm stats into layer state, Scale
    mapped onto the batch_norm params via --map. Mirrors reference
    tools/caffe_converter/convert.cpp:30-187 without needing Caffe."""
    rng = np.random.RandomState(0)
    wc = rng.randn(4, 3, 3, 3).astype(np.float32)        # OIHW
    bc = rng.randn(4).astype(np.float32)
    wip = rng.randn(3, 144).astype(np.float32)           # (out, in)
    bip = rng.randn(3).astype(np.float32)
    mean, var = rng.randn(4).astype(np.float32), rng.rand(4).astype(np.float32)
    gamma, beta = rng.randn(4).astype(np.float32), rng.randn(4).astype(np.float32)
    blob = (_caffe_layer_new("cv1", "Convolution", [wc, bc])
            + _caffe_layer_new("bn1", "BatchNorm",
                               [mean * 2.0, var * 2.0, np.asarray([2.0])])
            + _caffe_layer_new("scale1", "Scale", [gamma, beta])
            + _caffe_layer_new("ip1", "InnerProduct", [wip, bip]))
    src = tmp_path / "m.caffemodel"
    src.write_bytes(blob)
    conf = tmp_path / "net.conf"
    conf.write_text(CONV_CONF)
    out = tmp_path / "out.model"

    from import_weights import import_weights
    n = import_weights(str(conf), str(src), str(out), fmt="caffe",
                       rename={"scale1": "bn1"}, verbose=False)
    assert n == 8

    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer(parse_config_string(CONV_CONF + "dev = cpu\n"))
    tr.init_model()
    tr.load_model(str(out))
    # conv: BGR->RGB flip on input channels then OIHW -> HWIO
    np.testing.assert_allclose(tr.get_weight("cv1", "wmat"),
                               wc[:, ::-1].transpose(2, 3, 1, 0))
    np.testing.assert_allclose(tr.get_weight("cv1", "bias"), bc)
    # fullc transposed to (in, out)
    np.testing.assert_allclose(tr.get_weight("ip1", "wmat"), wip.T)
    # BN stats divided by the scale factor, landed in state
    np.testing.assert_allclose(tr.get_state("bn1", "running_exp"), mean,
                               rtol=1e-6)
    np.testing.assert_allclose(tr.get_state("bn1", "running_var"), var,
                               rtol=1e-6)
    # Scale layer mapped onto batch_norm gamma/beta
    np.testing.assert_allclose(tr.get_weight("bn1", "wmat"), gamma)
    np.testing.assert_allclose(tr.get_weight("bn1", "bias"), beta)


def test_import_caffemodel_v1_format(tmp_path):
    """Legacy V1LayerParameter (field 2, enum types, legacy NCHW blob
    dims) parses too — pretrained-era models use this encoding."""
    rng = np.random.RandomState(1)
    wc = rng.randn(2, 3, 3, 3).astype(np.float32)
    bc = rng.randn(2).astype(np.float32)
    blob = _caffe_layer_v1("cv1", 4, [wc, bc])          # 4 = CONVOLUTION
    src = tmp_path / "v1.caffemodel"
    src.write_bytes(blob)
    from import_caffe import caffe_to_keys, parse_caffemodel
    layers = parse_caffemodel(str(src))
    assert [(l["name"], l["type"]) for l in layers] == [("cv1", "Convolution")]
    keys = caffe_to_keys(layers, rgb_flip=False)
    np.testing.assert_allclose(keys["cv1.wmat"], wc.transpose(2, 3, 1, 0))
    np.testing.assert_allclose(keys["cv1.bias"], bc)


def test_import_nested_dotted_keys(tmp_path):
    """npz keys addressing nested mha params ('attn.q.wmat') resolve by
    longest-prefix layer matching."""
    lm_conf = """
netconfig=start
layer[+1:e0] = embed:emb
  nhidden = 16
  vocab_size = 8
layer[+1:a1] = mha:attn
  nhead = 2
layer[+1:lg] = seqfc:head
  nhidden = 8
layer[+0] = lmloss
netconfig=end
input_shape = 1,1,8
label_vec[0,8) = label
batch_size = 8
"""
    conf = tmp_path / "lm.conf"
    conf.write_text(lm_conf)
    w = np.full((16, 2, 8), 0.5, np.float32)
    npz = tmp_path / "w.npz"
    np.savez(npz, **{"attn.q.wmat": w})
    from import_weights import import_weights
    out = tmp_path / "out.model"
    n = import_weights(str(conf), str(npz), str(out), verbose=False)
    assert n == 1
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer(parse_config_string(lm_conf + "dev = cpu\n"))
    tr.init_model()
    tr.load_model(str(out))
    np.testing.assert_allclose(tr.get_weight("attn", "q.wmat"), w)


def test_dotted_weight_paths():
    """Nested (mha) params reachable through dotted tags."""
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.trainer import Trainer
    cfg = """
netconfig=start
layer[+1:e0] = embed:emb
  nhidden = 16
  vocab_size = 8
layer[+1:a1] = mha:attn
  nhead = 2
layer[+1:lg] = seqfc:head
  nhidden = 8
layer[+0] = lmloss
netconfig=end
input_shape = 1,1,8
label_vec[0,8) = label
batch_size = 8
dev = cpu
"""
    tr = Trainer(parse_config_string(cfg))
    tr.init_model()
    w = tr.get_weight("attn", "q.wmat")
    assert w.shape == (16, 2, 8)
    tr.set_weight(np.zeros_like(w), "attn", "q.wmat")
    assert np.all(tr.get_weight("attn", "q.wmat") == 0)


def test_test_io_mode(tmp_path):
    """test_io=1 runs the pipeline and reports throughput, never updating."""
    out = subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.main",
         os.path.join(REPO, "examples", "synthetic_mlp.conf"),
         "test_io=1", "num_round=2", f"model_dir={tmp_path}"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "test_io" in out.stdout and "images/sec" in out.stdout
    assert not any(f.endswith(".model") for f in os.listdir(tmp_path))


def test_allreduce_pairs_single_process_identity():
    from cxxnet_tpu.parallel import allreduce_metric_pairs
    pairs = [(1.5, 3), (0.25, 8)]
    assert allreduce_metric_pairs(pairs) == pairs


def test_two_process_distributed_training(tmp_path):
    """Real multi-process jax.distributed run (the ps-lite local-mode
    analog): 2 workers x 2 virtual CPU devices form one 4-device
    data-parallel mesh; both ranks must agree on globally-reduced metrics
    and converge like the single-process run."""
    out = subprocess.run(
        ["sh", "local_launch.sh", "2", "../synthetic_mlp.conf",
         "num_round=2", f"model_dir={tmp_path}"],
        capture_output=True, text=True,
        cwd=os.path.join(REPO, "examples", "multi-machine"),
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "CXXNET_CPU_DEVICES": "2"}, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [l for l in out.stdout.splitlines() if "train-error" in l]
    # rank 0 prints exactly one line per round; ranks >0 stay silent
    assert len(lines) == 2, out.stdout
    assert "train-error:0.0" in lines[-1]
    # rank-0-only checkpointing: exactly the two round files, once each
    assert sorted(f for f in os.listdir(tmp_path)
                  if f.endswith(".model")) == ["0000.model", "0001.model"]


def test_two_process_ring_attention(tmp_path):
    """Sequence parallelism across process boundaries: the 'seq' mesh axis
    spans 2 processes x 2 devices; ppermute carries k/v shards over the
    inter-process transport and every rank's local output must match the
    single-device reference."""
    import socket
    with socket.socket() as s:        # reserve a genuinely free port
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    cwd = os.path.join(REPO, "examples", "multi-machine")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "CXXNET_CPU_DEVICES": "2"}
    procs = [subprocess.Popen(
        [sys.executable, "ring_worker.py", f"localhost:{port}", "2", str(r)],
        cwd=cwd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for r in range(2)]
    try:
        outs = [p.communicate(timeout=240) for p in procs]
    finally:                          # no orphan workers on timeout/failure
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert all(p.returncode == 0 for p in procs), \
        [o[1][-2000:] for o in outs]
    assert "ring-attention x2proc causal=True ok" in outs[0][0]


# -- Caffe mean.binaryproto import (VERDICT r5 #6) ----------------------------

def _varint(v):
    out = b""
    while True:
        b7 = v & 0x7F
        v >>= 7
        out += bytes([b7 | (0x80 if v else 0)])
        if not v:
            return out


def _pb_field(num, wt, payload):
    return _varint((num << 3) | wt) + payload


def _blobproto(chw: "np.ndarray") -> bytes:
    """Legacy-dims BlobProto with packed float data (the layout real
    Caffe mean files use)."""
    c, h, w = chw.shape
    data = chw.astype("<f4").tobytes()
    return (_pb_field(1, 0, _varint(1)) + _pb_field(2, 0, _varint(c))
            + _pb_field(3, 0, _varint(h)) + _pb_field(4, 0, _varint(w))
            + _pb_field(5, 2, _varint(len(data)) + data))


def test_wire_reader_golden_fields():
    """io/augment's minimal protobuf reader (moved there from
    telemetry/traceparse.py in PR 24, its one other user gone): every
    wire type it knows, byte for byte."""
    import struct
    from cxxnet_tpu.io.augment import iter_fields, read_varint
    assert read_varint(b"\x01", 0) == (1, 1)
    assert read_varint(b"\xac\x02", 0) == (300, 2)         # two bytes
    assert read_varint(b"\x00\xff\xff\xff\xff\x0f", 1) == (2 ** 32 - 1, 6)
    msg = (_pb_field(1, 0, _varint(150))
           + _pb_field(2, 1, struct.pack("<d", 2.5))
           + _pb_field(3, 2, _varint(3) + b"abc")
           + _pb_field(4, 5, struct.pack("<f", 1.5))
           + _pb_field(16, 0, _varint(2 ** 40)))            # two-byte key
    got = list(iter_fields(msg))
    assert [(f, wt) for f, wt, _ in got] == [(1, 0), (2, 1), (3, 2),
                                             (4, 5), (16, 0)]
    assert got[0][2] == 150 and got[4][2] == 2 ** 40
    assert struct.unpack("<d", got[1][2])[0] == 2.5
    assert got[2][2] == b"abc"
    assert struct.unpack("<f", got[3][2])[0] == 1.5
    assert list(iter_fields(b"")) == []


def test_wire_reader_nested_and_unsupported():
    from cxxnet_tpu.io.augment import iter_fields
    inner = _pb_field(1, 0, _varint(7)) + _pb_field(1, 0, _varint(9))
    outer = _pb_field(7, 2, _varint(len(inner)) + inner)
    (field, wt, val), = iter_fields(outer)
    assert (field, wt) == (7, 2)
    assert [v for _, _, v in iter_fields(val)] == [7, 9]
    with pytest.raises(ValueError, match="wire type 3"):
        list(iter_fields(_pb_field(1, 3, b"")))             # group start


def test_binaryproto_mean_parse_and_flip():
    from cxxnet_tpu.io.augment import load_binaryproto_mean
    chw = np.arange(3 * 4 * 4, dtype=np.float32).reshape(3, 4, 4)
    m = load_binaryproto_mean(_blobproto(chw))
    assert m.shape == (4, 4, 3) and m.dtype == np.float32
    # Caffe blobs are BGR: output channel 0 must be input channel 2
    assert np.array_equal(m[:, :, 0], chw[2])
    assert np.array_equal(m[:, :, 2], chw[0])
    m2 = load_binaryproto_mean(_blobproto(chw), rgb_flip=False)
    assert np.array_equal(m2[:, :, 0], chw[0])


def test_binaryproto_meanstore_center_crop(tmp_path):
    """image_mean = *.binaryproto loads directly; a resize-sized mean
    (Caffe's 256x256 convention) center-crops to the input shape."""
    from cxxnet_tpu.io.augment import MeanStore
    chw = np.arange(3 * 6 * 6, dtype=np.float32).reshape(3, 6, 6)
    p = tmp_path / "mean.binaryproto"
    p.write_bytes(_blobproto(chw))
    ms = MeanStore(str(p), (4, 4, 3))
    assert ms.ready and ms.mean.shape == (4, 4, 3)
    hwc = np.transpose(chw, (1, 2, 0))[:, :, ::-1]
    assert np.array_equal(ms.mean, hwc[1:5, 1:5])


def test_binaryproto_mean_bad_shape():
    from cxxnet_tpu.io.augment import load_binaryproto_mean
    with pytest.raises(ValueError):
        load_binaryproto_mean(_pb_field(1, 0, _varint(1)))


def test_import_caffe_mean_cli(tmp_path):
    chw = (np.random.RandomState(0).rand(3, 5, 5) * 255).astype(
        np.float32)
    src = tmp_path / "mean.binaryproto"
    src.write_bytes(_blobproto(chw))
    dst = tmp_path / "mean.npy"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "import_caffe.py"),
         "--mean", str(src), str(dst)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    out = np.load(dst)
    assert out.shape == (5, 5, 3)
    assert np.allclose(out, np.transpose(chw, (1, 2, 0))[:, :, ::-1])
