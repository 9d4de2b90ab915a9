"""The main path's programs, compiled for a TPU v5e that is described
and not attached (on-chip-measurement guide, section 2.3).

The CPU backend — what every other test here runs — accepts programs
the chip's compiler refuses (the v5e has no bf16 vector compare; tiles
must align; VMEM is finite). These tests hand the installed TPU
compiler each convnet layer's own ``apply``, forward and gradient, and
the attention kernels, at the widths of the checked-in example configs,
so a refusal costs a test failure here and not chip time. For the
convnet layers they also pin what PR 30 left: one implementation per
op, XLA's own code — no ``tpu_custom_call`` and no host callback in the
compiled text, on one chip and on the four-chip mesh under GSPMD.
Nothing runs: a pass says "compiles", never "correct" or "fast".

Everything that touches the topology lives in the module-scoped,
non-autouse fixtures below, so only the xdist worker that is handed
this file loads the TPU library, every worker collects the same tests,
and no child process is started.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cxxnet_tpu.config import parse_config_string
from cxxnet_tpu.graph import build_graph
from cxxnet_tpu.model import Network
from cxxnet_tpu.ops.attention import flash_attention, paged_attention
from cxxnet_tpu.ops.quant import int8_matmul
from cxxnet_tpu.ops.stem import decode_normalize
from cxxnet_tpu.optim import create_optimizer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BF16, F32 = jnp.bfloat16, jnp.float32
DTYPES = [pytest.param(BF16, id="bf16"), pytest.param(F32, id="f32")]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else it logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an executable compiled for a described chip is written to the
        # persistent cache but cannot be read back without one
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """A 1-device mesh of the described chip, in the trainer's axes."""
    return Mesh(np.asarray(topo.devices[:1]).reshape(1, 1, 1, 1),
                ("data", "pipe", "seq", "model"))


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices).reshape(4, 1, 1, 1),
                ("data", "pipe", "seq", "model"))


def _is_sd(a) -> bool:
    """A (shape, dtype) pair: the leaf of the arg trees below."""
    return isinstance(a, tuple) and len(a) == 2 and isinstance(a[0], tuple)


def _compile(fn, mesh, args, grad_argnums=(), specs=None):
    """Compile ``fn`` — and, with ``grad_argnums``, the gradient of its
    (first) output's sum — for ``mesh``'s described devices; returns
    the compiled text. ``args``: (shape, dtype) pairs or pytrees of
    them; ``specs``: one PartitionSpec per arg (default replicated)."""
    specs = specs or [P()] * len(args)
    structs = [jax.tree_util.tree_map(
        lambda sd, _s=spec: jax.ShapeDtypeStruct(
            sd[0], sd[1], sharding=NamedSharding(mesh, _s)),
        a, is_leaf=_is_sd) for a, spec in zip(args, specs)]

    def first(out):
        return out[0] if isinstance(out, (tuple, list)) else out

    def run(*a):
        if not grad_argnums:
            return fn(*a)
        loss = lambda *b: jnp.sum(first(fn(*b)).astype(F32))
        return jax.value_and_grad(loss, argnums=grad_argnums)(*a)
    return jax.jit(run).lower(*structs).compile().as_text()


def _kernels(text: str) -> int:
    return text.count("tpu_custom_call")


# -- the convnet layers: XLA's own code, and the chip's compiler takes it -------

def _net(body: str, input_shape, dtype):
    c, h, w = input_shape
    cdt = "bfloat16" if dtype == BF16 else "float32"
    cfg = parse_config_string(
        f"netconfig=start\n{body}\nnetconfig=end\n"
        f"input_shape = {c},{h},{w}\ncompute_dtype = {cdt}\n")
    g = build_graph(cfg)
    return Network(g, g.defcfg)


def _xla_only(text: str) -> None:
    assert _kernels(text) == 0
    assert "callback" not in text.lower()


def _compile_layers(body, batch, input_shape, dtype, mesh, sharded=False):
    """Compile forward and gradient (w.r.t. parameters and input) of a
    training-mode pass through the layers of ``body`` at ``batch`` rows
    of ``input_shape`` (c, h, w); returns the compiled text."""
    net = _net(body, input_shape, dtype)
    params, state = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    sd = lambda t: jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), a.dtype), t)
    c, h, w = input_shape
    fn = lambda p, st, x: net.apply(p, st, x, train=True).out
    return _compile(fn, mesh, [sd(params), sd(state),
                               ((batch, h, w, c), dtype)],
                    grad_argnums=(0, 2),
                    specs=[P(), P(), P("data") if sharded else P()])


_BN_RELU = "layer[0->1] = batch_norm:bn\nlayer[1->2] = relu:ac"

# Inception-BN b256 (examples/ImageNet/inception_bn.conf)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [
    (256, 112, 112, 64),      # stem1
    (256, 56, 56, 192),       # stem2
    (256, 28, 28, 96),        # 3a/3b branches
    (256, 14, 14, 160),       # 4c
    (256, 7, 7, 352),         # 5a/5b 1x1 (C not a lane multiple)
], ids=lambda s: "x".join(map(str, s)))
def test_batch_norm_relu_compiles(one_chip, shape, dtype):
    b, h, w, c = shape
    _xla_only(_compile_layers(_BN_RELU, b, (c, h, w), dtype, one_chip))


@pytest.mark.parametrize("dtype", DTYPES)
def test_global_avg_pooling_compiles(one_chip, dtype):
    body = "layer[0->1] = avg_pooling:gap\n  kernel_size = 7\n  stride = 1"
    _xla_only(_compile_layers(body, 256, (1024, 7, 7), dtype, one_chip))


def _leaves_of(conf_text: str):
    cfg = parse_config_string(conf_text)
    shapes = Network(build_graph(cfg), cfg).param_shapes()
    return jax.tree_util.tree_map(lambda a: (tuple(a.shape), F32), shapes)


_LM_CONF = os.path.join(_REPO, "examples", "LM", "long_context_lm.conf")


@pytest.mark.parametrize("updater", ["sgd", "adam"])
def test_optimizer_update_compiles_at_real_leaves(one_chip, updater):
    """The per-leaf update over the flagship's parameter tree (sgd) and
    the long-context LM's (adam): one program, no kernel."""
    if updater == "sgd":
        sys.path.insert(0, os.path.join(_REPO, "examples", "ImageNet"))
        try:
            from gen_inception_bn import generate
        finally:
            sys.path.pop(0)
        leaves = _leaves_of(generate(with_data=False))
        assert len(jax.tree_util.tree_leaves(leaves)) > 400   # 2 a leaf
    else:
        with open(_LM_CONF) as f:
            leaves = _leaves_of(f.read())
    opt = create_optimizer(updater, [("eta", "0.01"), ("wd", "0.0001")])
    as_struct = lambda t: jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(*sd), t, is_leaf=_is_sd)
    state = jax.eval_shape(opt.init_state, as_struct(leaves))
    state = jax.tree_util.tree_map(lambda a: (tuple(a.shape), a.dtype),
                                   state)
    sched = {tag: [((), F32), ((), F32)] for tag in ("wmat", "bias")}
    fn = lambda p, g, st, sc: opt.update(p, g, st, sc)
    _xla_only(_compile(fn, one_chip, [leaves, leaves, state, sched]))


@pytest.mark.parametrize("dtype", DTYPES)
def test_decode_normalize_compiles(one_chip, dtype):
    fn = lambda x, mean, f: decode_normalize(x, mean, f, dtype)
    _xla_only(_compile(fn, one_chip, [((256, 224, 224, 3), jnp.uint8),
                                      ((224, 224, 3), F32), ((), F32)]))


# AlexNet b256 / kaggle_bowl b64 / digits (bias+relu, LRN, tiled pool)

def _conv(k, n, stride=1, pad=0, extra=""):
    return (f"layer[0->1] = conv:cv\n  kernel_size = {k}\n"
            f"  nchannel = {n}\n  stride = {stride}\n  pad = {pad}\n{extra}"
            "layer[1->2] = relu:ac")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("body,batch,input_shape", [
    (_conv(11, 96, stride=4), 256, (3, 227, 227)),     # alexnet cv1
    (_conv(3, 384, pad=1), 256, (256, 13, 13)),        # alexnet cv3
    ("layer[0->1] = fullc:fc\n  nhidden = 4096\n"      # alexnet fc6
     "layer[1->2] = relu:ac", 256, (256, 6, 6)),
    (_conv(1, 96), 256, (192, 28, 28)),                # inception 3a 1x1
    (_conv(4, 48, pad=2), 64, (1, 40, 40)),            # kaggle_bowl cv1
], ids=["alexnet_cv1", "alexnet_cv3", "alexnet_fc6", "ibn_3a_1x1",
        "bowl_cv1"])
def test_bias_relu_producers_compile(one_chip, body, batch, input_shape,
                                     dtype):
    _xla_only(_compile_layers(body, batch, input_shape, dtype, one_chip))


@pytest.mark.parametrize("dtype", DTYPES)
def test_conv_without_bias_then_relu_compiles(one_chip, dtype):
    body = _conv(5, 256, pad=2, extra="  no_bias = 1\n  ngroup = 2\n")
    _xla_only(_compile_layers(body, 256, (96, 27, 27), dtype, one_chip))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind,batch,input_shape", [
    ("max_pooling", 256, (192, 56, 56)),      # ImageNet-class width, 2x2/2
    ("relu_max_pooling", 256, (192, 56, 56)),
    ("max_pooling", 128, (32, 8, 8)),         # digits_lenet.conf mp1
], ids=["56x56x192", "56x56x192-prerelu", "digits"])
def test_max_pooling_compiles(one_chip, kind, batch, input_shape, dtype):
    body = f"layer[0->1] = {kind}:mp\n  kernel_size = 2\n  stride = 2"
    _xla_only(_compile_layers(body, batch, input_shape, dtype, one_chip))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("input_shape", [(96, 27, 27), (256, 13, 13)],
                         ids=["lrn1", "lrn2"])
def test_lrn_compiles(one_chip, input_shape, dtype):
    body = ("layer[0->1] = lrn:lrn\n  local_size = 5\n  alpha = 0.0001\n"
            "  beta = 0.75\n  knorm = 1")
    _xla_only(_compile_layers(body, 256, input_shape, dtype, one_chip))


@pytest.mark.parametrize("k,n", [(9216, 4096), (4096, 4096)],
                         ids=["fc6", "fc7"])
def test_int8_matmul_compiles(one_chip, k, n):
    """serve_dtype = int8 on AlexNet's big FCs at a /predict bucket."""
    fn = lambda x, wq, ws, s, b: int8_matmul(x, wq, ws, s, b, "relu")
    _xla_only(_compile(fn, one_chip, [
        ((32, k), F32), ((k, n), jnp.int8), ((n,), F32), ((), F32),
        ((n,), F32)]))


# four chips, the batch sharded over 'data': GSPMD's own collectives

@pytest.mark.parametrize("dtype", DTYPES)
def test_sync_bn_compiles_on_four_chips(four_chips, dtype):
    text = _compile_layers(_BN_RELU, 256, (192, 56, 56), dtype, four_chips,
                           sharded=True)
    _xla_only(text)
    assert "all-reduce" in text         # the batch moments, over the mesh


@pytest.mark.parametrize("dtype", DTYPES)
def test_conv_bias_relu_pool_compile_on_four_chips(four_chips, dtype):
    body = (_conv(3, 96, pad=1) + "\nlayer[2->3] = max_pooling:mp\n"
            "  kernel_size = 2\n  stride = 2")
    text = _compile_layers(body, 256, (96, 56, 56), dtype, four_chips,
                           sharded=True)
    _xla_only(text)
    assert "all-reduce" in text         # dbias and dw, over the mesh

# -- long_context_lm.conf (flash attention, paged decode) -------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(8, 2048, 8, 16), (4, 2048, 8, 64)],
                         ids=lambda s: "x".join(map(str, s)))
def test_flash_attention_compiles(one_chip, shape, dtype):
    fn = lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128,
                                         False)
    text = _compile(fn, one_chip, [(shape, dtype)] * 3,
                    grad_argnums=(0, 1, 2))
    assert _kernels(text) == 2          # forward, backward


# -- benchmarks/configs/joyai_llm_flash.conf (latent attention at 8k) ----------

def test_latent_attention_flash_compiles_at_the_cell_s_widths(one_chip):
    """One row of 8192 positions, 32 heads, q.k 192 wide (no multiple
    of the 128 lanes) and v 128 wide in its own right, blocks of 1024
    forward and backward (they won both A/Bs on the chip): the shape
    ``mla`` hands the kernel in ``joyai_ep16_train_8k``. The backward
    holds a head's whole float32 dq row in VMEM (8192 x 256 lanes,
    twice) under a limit raised for it: an overflow fails here."""
    fn = lambda q, k, v: flash_attention(q, k, v, True, None, 1024, 1024,
                                         False)
    text = _compile(fn, one_chip, [((1, 8192, 32, 192), BF16)] * 2
                    + [((1, 8192, 32, 128), BF16)], grad_argnums=(0, 1, 2))
    assert _kernels(text) == 2          # forward, backward


# -- benchmarks/configs/laguna_s_2_1.conf (grouped heads, a window, at 8k) -------

@pytest.mark.parametrize("heads, window, block", [
    pytest.param(24, None, 1024, id="full"),
    pytest.param(36, 512, 512, id="window"),
    pytest.param(36, 512, 256, id="window256"),
    pytest.param(36, 768, 512, id="window768")])
def test_grouped_query_flash_compiles_at_the_cell_s_widths(
        one_chip, heads, window, block):
    """One row of 8192 positions, heads of 128, 4 key/value heads read
    by 24 (a full layer) or 36 (a window layer, band 512) query heads:
    the shapes ``gqa`` hands the kernel in ``laguna_ep32_train_8k``. The
    index maps divide a grid row by the group and, with a window, clamp
    a band's block: a map the chip's compiler refuses fails here. A band
    of 768 at blocks of 512 has its trailing edge at two offsets: four
    bodies a kernel, the most the tile classes give."""
    fn = lambda q, k, v: flash_attention(q, k, v, True, None, block, block,
                                         False, window)
    text = _compile(fn, one_chip, [((1, 8192, heads, 128), BF16)]
                    + [((1, 8192, 4, 128), BF16)] * 2,
                    grad_argnums=(0, 1, 2))
    assert _kernels(text) == 2          # forward, backward


@pytest.mark.parametrize("sub", (128, 256, 512))
@pytest.mark.parametrize("heads, kv_heads, d, window, block", [
    pytest.param(32, 32, 192, None, 1024, id="mla"),
    pytest.param(36, 4, 128, 512, 512, id="gqa_window")])
def test_the_edge_tiles_compile_at_every_sub_tile_of_the_sweep(
        one_chip, monkeypatch, heads, kv_heads, d, window, block, sub):
    """The two shapes the sequence cells run, forward and backward, with
    an edge tile taken by sub-tiles of 128, 256 and 512 (PERF.md section
    6, PR 33, has the chip's sweep; ``_subtile`` holds the rule it set):
    static slices of the resident q, k, v and cotangent tiles, of the
    accumulators' rows and of the logsumexp's lanes, which the chip's
    compiler refuses where they do not align. At the shapes run the
    split engages: some sub-tiles are left out, some carry no mask."""
    from cxxnet_tpu.ops import attention
    monkeypatch.setattr(attention, "_subtile", lambda b: min(sub, b))
    cls = attention.flash_tile_classes(8192, block, window)
    assert cls["subtile"] == min(sub, block)
    if sub < block:
        assert cls["sub_skipped"] and cls["sub_masked"]
    fn = lambda q, k, v: flash_attention(q, k, v, True, None, block, block,
                                         False, window)
    text = _compile(fn, one_chip, [((1, 8192, heads, d), BF16),
                                   ((1, 8192, kv_heads, d), BF16),
                                   ((1, 8192, kv_heads, 128), BF16)],
                    grad_argnums=(0, 1, 2))
    assert _kernels(text) == 2          # forward, backward


# -- benchmarks/configs/keye_vl_2_0_30b_a3b.conf (selected keys, at 8k) ----------

def test_sparse_attention_kernels_compile_at_the_cell_s_widths(one_chip):
    """One row of 8192 positions, 32 query heads of 128 over 4 key/value
    heads, the selection an int8 a pair, blocks of 1024: what ``dsa``
    hands ``flash_attention_select`` in ``keye_ep16_train_8k``. The
    tiles' table reaches the index maps by scalar prefetch and the int8
    tile is compared as float32: either refused by the chip's compiler
    fails here."""
    from cxxnet_tpu.ops.attention import flash_attention_select
    fn = lambda q, k, v, sel: flash_attention_select(
        q, k, v, sel, None, 1024, 1024, False)[0]
    text = _compile(fn, one_chip, [((1, 8192, 32, 128), BF16)]
                    + [((1, 8192, 4, 128), BF16)] * 2
                    + [((1, 8192, 8192), jnp.int8)], grad_argnums=(0, 1, 2))
    assert _kernels(text) == 2          # forward, backward


def test_the_indexer_s_kernels_compile_at_the_cell_s_widths(one_chip):
    """The indexer's scores (16 heads of 64 against one key head, tiles
    of 512), the head-summed distribution over the selected set (32
    heads innermost, a float32 tile of 1024 x 1024 resident across
    them), and the exact selection: one kernel, ``select_rows`` — 128
    rows of 8192 scores, their integer image and their int8 set resident
    — inside the VMEM limit it asks for; its oracle,
    ``select_topk_reference``, is XLA's own loops, no sort."""
    from cxxnet_tpu.ops.attention import (_BWD_VMEM_LIMIT, SELECT_ROWS,
                                          _select_rows_vmem, head_sum_probs,
                                          index_scores, select_rows,
                                          select_rows_block,
                                          select_topk_reference)
    text = _compile(lambda qi, ki, w: index_scores(qi, ki, w, 512, False),
                    one_chip, [((1, 8192, 16, 64), BF16),
                               ((1, 8192, 64), BF16), ((1, 8192, 16), F32)])
    assert _kernels(text) == 1
    text = _compile(
        lambda q, k, lse, sel: head_sum_probs(q, k, lse, sel, None, 1024,
                                              False),
        one_chip, [((1, 8192, 32, 128), BF16), ((1, 8192, 4, 128), BF16),
                   ((32, 8192), F32), ((1, 8192, 8192), jnp.int8)])
    assert _kernels(text) == 1
    assert select_rows_block(8192) == SELECT_ROWS
    assert _select_rows_vmem(SELECT_ROWS, 8192) <= _BWD_VMEM_LIMIT
    text = _compile(lambda s: select_rows(s, 2048, SELECT_ROWS, False),
                    one_chip, [((1, 8192, 8192), F32)])
    assert _kernels(text) == 1
    assert text.count('"size":"%d"' % _BWD_VMEM_LIMIT) == 1
    text = _compile(lambda s: select_topk_reference(s, 2048).astype(jnp.int8),
                    one_chip, [((1, 8192, 8192), F32)])
    _xla_only(text)
    assert "sort" not in text


def test_the_indexer_s_backward_kernel_compiles_at_the_cell_s_widths(one_chip):
    """The gradient of the indexer's scores at the cell's widths: two
    kernels, ``index_scores`` and ``index_scores_bwd`` — the key head's
    whole float32 gradient row resident (8192 x 128 lanes, two buffers),
    sixteen heads' blocks of 512 beside it, the cotangent's tile turned
    in the kernel — inside the VMEM limit the kernel asks for, which is
    over the compiler's default."""
    from cxxnet_tpu.ops.attention import _BWD_VMEM_LIMIT, index_scores
    text = _compile(lambda qi, ki, w: index_scores(qi, ki, w, 512, False),
                    one_chip, [((1, 8192, 16, 64), BF16),
                               ((1, 8192, 64), BF16), ((1, 8192, 16), F32)],
                    grad_argnums=(0, 1, 2))
    assert _kernels(text) == 2          # forward, backward
    # the backward's custom call carries the scoped limit it asked for
    assert text.count('"size":"%d"' % _BWD_VMEM_LIMIT) == 1


# -- benchmarks/configs/lfm2_8b_a1b.conf (heads of 64, short convolutions) -------

def test_grouped_query_flash_compiles_at_a_head_of_64(one_chip):
    """One row of 8192 positions, 32 query heads of 64 over 8 key/value
    heads, blocks of 1024: what ``gqa`` hands the kernels in
    ``lfm2_ep4_train_8k``. A head of 64 is half the lanes: the tiles'
    last dimension is the array's whole, and the backward's resident dq
    row is padded to 128 lanes in its VMEM count."""
    fn = lambda q, k, v: flash_attention(q, k, v, True, None, 1024, 1024,
                                         False)
    text = _compile(fn, one_chip, [((1, 8192, 32, 64), BF16)]
                    + [((1, 8192, 8, 64), BF16)] * 2,
                    grad_argnums=(0, 1, 2))
    assert _kernels(text) == 2          # forward, backward


def test_the_short_convolution_compiles_at_the_cell_s_widths(one_chip):
    """A ``shortconv`` layer of ``lfm2_ep4_train_8k`` — 8192 positions of
    2048, 3 taps — forward and gradient under ``jax.checkpoint``: XLA's
    own code, and both projections' products carry the scope
    ``shortconv.proj`` in the compiled text."""
    from cxxnet_tpu.graph import LayerSpec
    from cxxnet_tpu.layers import ApplyCtx, create_layer
    from cxxnet_tpu.telemetry.traceparse import scope_table
    n, e = 8192, 2048
    layer = create_layer(LayerSpec("shortconv", "b0_conv", [0], [1], [
        ("conv_L_cache", "3")]), [])
    params = jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), a.dtype), jax.eval_shape(
            lambda key: layer.init_params(key, [(e, n, 1)]),
            jax.random.PRNGKey(0)))

    @jax.checkpoint
    def fn(p, xs):
        with jax.named_scope("b0_conv"):
            return layer.apply(p, {}, [xs], ApplyCtx(
                train=True, compute_dtype=BF16))[0][0]
    text = _compile(fn, one_chip, [params, ((1, n, 1, e), BF16)],
                    grad_argnums=(0, 1))
    _xla_only(text)
    scopes = scope_table(text)
    dots = [op for op in scopes.values() if "dot_general" in op]
    assert dots and all("shortconv.proj" in op for op in dots), dots


def test_the_held_experts_ladder_compiles_at_the_cell_s_sizes(one_chip):
    """An expert layer of ``joyai_ep16_train_8k`` — 8192 positions of
    2048, top-8 of 256 with 16 held, experts 768 wide — forward and
    gradient under ``jax.checkpoint``: the chip's compiler takes the
    four rungs' grouped kernels, the rebuilt forward's switch is dead
    code (two conditionals, not three), and what crosses a conditional
    is sized by the positions and the weights alone — no rung's buffer,
    and the weights' gradients still in bf16 there (their casts to
    float32 stay outside, where they fuse into the optimizer's
    update)."""
    import re
    from cxxnet_tpu.graph import LayerSpec
    from cxxnet_tpu.layers import ApplyCtx, create_layer
    from cxxnet_tpu.layers.moe import buffer_ladder
    n, e, f, x, held, k = 8192, 2048, 768, 256, 16, 8
    rungs = buffer_ladder(n, k, held, x)
    assert rungs == (8192, 16384, 32768, 65536)
    layer = create_layer(LayerSpec("moe", "L", [0], [1], [
        ("router", "sigmoid"), ("num_expert", str(x)), ("topk", str(k)),
        ("nhidden", str(f)), ("shared_expert", "1"),
        ("expert_first", "80"), ("expert_held", str(held))]), [])
    sd = lambda t: jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), a.dtype), t)
    params = sd(jax.eval_shape(
        lambda key: layer.init_params(key, [(e, n, 1)]),
        jax.random.PRNGKey(0)))
    state = sd(jax.eval_shape(lambda: layer.init_state([(e, n, 1)])))

    @jax.checkpoint
    def fn(p, st, xs):
        return layer.apply(p, st, [xs], ApplyCtx(
            train=True, compute_dtype=BF16))[0][0]
    text = _compile(fn, one_chip, [params, state, ((1, n, 1, e), BF16)],
                    grad_argnums=(0, 2))
    results = re.findall(r"^\s*%\S+ = (\(.*?\)) conditional\(", text, re.M)
    assert len(results) == 2
    for shapes in results:
        leading = {int(d) for d in re.findall(r"\[(\d+)[,\]]", shapes)}
        assert not leading & (set(rungs[1:]) | {n * k}), shapes
    backward = max(results, key=len)
    assert backward.count(f"bf16[{held},") == 3, backward
    assert _kernels(text) % len(rungs) == 0


@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_decode_attention_compiles(one_chip, dtype):
    """serve/lm's decode step: plain XLA (no kernel), still the chip's
    compiler — 4 sequences over a 2048-token context in 16-token blocks."""
    fn = lambda q, kp, vp, tb, pos, ln: paged_attention(
        q, kp, vp, tb, pos, ln)
    pool = ((513, 16, 8, 16), dtype)
    _compile(fn, one_chip, [((4, 1, 8, 16), dtype), pool, pool,
                            ((4, 128), jnp.int32), ((4, 1), jnp.int32),
                            ((4,), jnp.int32)])


