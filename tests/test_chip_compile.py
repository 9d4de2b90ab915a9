"""The main path's Pallas kernels, compiled for a TPU v5e that is
described and not attached (on-chip-measurement guide, section 2.3).

Interpret mode — what every other kernel test here runs — accepts
programs the chip's compiler refuses (the v5e has no bf16 vector
compare; tiles must align; VMEM is finite). These tests hand the
installed TPU compiler each kernel, forward and backward, at the widths
of the checked-in example configs, so a refusal costs a test failure
here and not chip time. Nothing runs: a pass says "compiles", never
"correct" or "fast".

Everything that touches the topology lives in the module-scoped,
non-autouse fixtures below, so only the xdist worker that is handed
this file loads the TPU library, every worker collects the same tests,
and no child process is started.
"""

import functools
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
from cxxnet_tpu.ops.fused import FusedSpmd
from cxxnet_tpu.ops.fused_epilogue import fused_bias_act
from cxxnet_tpu.ops.fused_lrn import fused_lrn
from cxxnet_tpu.ops.fused_norm import fused_bn_act
from cxxnet_tpu.ops.fused_optim import fused_adam_apply, fused_sgd_apply
from cxxnet_tpu.ops.fused_pool import fused_pool
from cxxnet_tpu.ops.fused_quant import int8_matmul
from cxxnet_tpu.ops.fused_stem import fused_decode_normalize

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


def _compile(fn, mesh, args, grad_argnums=(), specs=None):
    """Compile ``fn`` — and, with ``grad_argnums``, the gradient of its
    (first) output's sum — for ``mesh``'s described devices; returns
    the compiled text. ``args``: (shape, dtype) pairs or pytrees of
    them; ``specs``: one PartitionSpec per arg (default replicated)."""
    specs = specs or [P()] * len(args)
    is_sd = lambda a: isinstance(a, tuple) and len(a) == 2 \
        and isinstance(a[0], tuple)
    structs = [jax.tree_util.tree_map(
        lambda sd, _s=spec: jax.ShapeDtypeStruct(
            sd[0], sd[1], sharding=NamedSharding(mesh, _s)),
        a, is_leaf=is_sd) for a, spec in zip(args, specs)]

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


# -- Inception-BN b256 (examples/ImageNet/inception_bn.conf) -------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [
    (256, 112, 112, 64),      # stem1
    (256, 56, 56, 192),       # stem2
    (256, 28, 28, 96),        # 3a/3b branches
    (256, 14, 14, 160),       # 4c
    (256, 7, 7, 352),         # 5a/5b 1x1 (C not a lane multiple)
], ids=lambda s: "x".join(map(str, s)))
def test_bn_act_relu_compiles(one_chip, shape, dtype):
    c = shape[-1]
    fn = lambda x, g, b: fused_bn_act(x, g, b, eps=1e-10, act="relu",
                                      interpret=False)
    text = _compile(fn, one_chip, [(shape, dtype), ((c,), F32), ((c,), F32)],
                    grad_argnums=(0, 1, 2))
    assert _kernels(text) >= 2          # forward + backward


@pytest.mark.parametrize("dtype", DTYPES)
def test_pool_global_avg_compiles(one_chip, dtype):
    fn = lambda x: fused_pool(x, 7, 7, 1, (0, 0), (0, 0), "sum", True,
                              False, interpret=False)
    text = _compile(fn, one_chip, [((256, 7, 7, 1024), dtype)],
                    grad_argnums=(0,))
    assert _kernels(text) >= 2


def _inception_leaves():
    """The flagship's real parameter leaves, grouped as the optimizer
    groups them (one fused apply per tag)."""
    sys.path.insert(0, os.path.join(_REPO, "examples", "ImageNet"))
    try:
        from gen_inception_bn import generate
    finally:
        sys.path.pop(0)
    cfg = parse_config_string(generate(with_data=False))
    shapes = Network(build_graph(cfg), cfg).param_shapes()
    by_tag = {}
    for layer in shapes.values():
        for tag, leaf in layer.items():
            by_tag.setdefault(tag, []).append((tuple(leaf.shape), F32))
    return by_tag


def test_sgd_apply_compiles_at_flagship_leaves(one_chip):
    by_tag = _inception_leaves()
    assert sum(map(len, by_tag.values())) > 200
    for tag, leaves in by_tag.items():
        fn = lambda ws, gs, ms, lr, mom: fused_sgd_apply(
            ws, gs, ms, lr, mom, wd=1e-4, clip=0.0, nag=False,
            interpret=False)
        text = _compile(fn, one_chip,
                        [leaves, leaves, leaves, ((), F32), ((), F32)])
        assert _kernels(text) == 1, tag


@pytest.mark.parametrize("dtype", DTYPES)
def test_stem_decode_normalize_compiles(one_chip, dtype):
    fn = lambda x, mean, f: fused_decode_normalize(
        x, mean, f, dtype, interpret=False)
    text = _compile(fn, one_chip, [((256, 224, 224, 3), jnp.uint8),
                                   ((224, 224, 3), F32), ((), F32)])
    assert _kernels(text) == 1


# -- AlexNet b256 / kaggle_bowl b64 / digits (bias+relu, LRN, tiled pool) ------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [
    (256, 55, 55, 96),        # alexnet cv1
    (256, 13, 13, 384),       # alexnet cv3
    (256, 1, 1, 4096),        # alexnet fc6/fc7 as a flat node
    (256, 28, 28, 96),        # the shape the v5e's compiler first refused
    (64, 41, 41, 48),         # kaggle_bowl cv1
], ids=lambda s: "x".join(map(str, s)))
def test_bias_act_relu_compiles(one_chip, shape, dtype):
    c = shape[-1]
    fn = lambda x, b: fused_bias_act(x, b, "relu", interpret=False)
    text = _compile(fn, one_chip, [(shape, dtype), ((c,), F32)],
                    grad_argnums=(0, 1))
    assert _kernels(text) >= 2


@pytest.mark.parametrize("dtype", DTYPES)
def test_act_only_epilogue_compiles(one_chip, dtype):
    fn = lambda x: fused_bias_act(x, None, "relu", interpret=False)
    text = _compile(fn, one_chip, [((256, 27, 27, 256), dtype)],
                    grad_argnums=(0,))
    assert _kernels(text) >= 2


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape,pre_relu", [
    ((256, 56, 56, 192), False),   # ImageNet-class width, 2x2/2
    ((256, 56, 56, 192), True),    # relu_max_pooling fold
    ((128, 8, 8, 32), False),      # examples/digits/digits_lenet.conf mp1
], ids=["56x56x192", "56x56x192-prerelu", "digits"])
def test_pool_max_tile_compiles(one_chip, shape, pre_relu, dtype):
    fn = lambda x: fused_pool(x, 2, 2, 2, (0, 0), (0, 0), "max", False,
                              pre_relu, interpret=False)
    text = _compile(fn, one_chip, [(shape, dtype)], grad_argnums=(0,))
    assert _kernels(text) >= 2


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(256, 27, 27, 96), (256, 13, 13, 256)],
                         ids=["lrn1", "lrn2"])
def test_lrn_compiles(one_chip, shape, dtype):
    fn = lambda x: fused_lrn(x, 5, 1e-4, 0.75, 1.0, interpret=False)
    text = _compile(fn, one_chip, [(shape, dtype)], grad_argnums=(0,))
    assert _kernels(text) >= 2


@pytest.mark.parametrize("k,n", [(9216, 4096), (4096, 4096)],
                         ids=["fc6", "fc7"])
def test_int8_matmul_compiles(one_chip, k, n):
    """serve_dtype = int8 on AlexNet's big FCs at a /predict bucket."""
    fn = lambda x, wq, ws, s, b: int8_matmul(
        x, wq, ws, s, b, "relu", fused=True, interpret=False)
    text = _compile(fn, one_chip, [((32, k), F32), ((k, n), jnp.int8),
                                   ((n,), F32), ((), F32), ((n,), F32)])
    assert _kernels(text) == 1


# -- long_context_lm.conf (flash attention, adam, paged decode) ----------------

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


def test_adam_apply_compiles_at_lm_leaves(one_chip):
    leaves = [(s, F32) for s in [
        (64, 128), (128,), (128,), (128, 8, 16), (8, 16), (8, 16, 128),
        (128, 512), (512,), (512, 128), (128, 64), (64,)]]
    fn = lambda ws, gs, a, b, lr: fused_adam_apply(
        ws, gs, a, b, lr, wd=0.0, clip=0.0, d1=0.1, d2=0.001,
        interpret=False)
    text = _compile(fn, one_chip, [leaves] * 4 + [((), F32)])
    assert _kernels(text) == 1


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


# -- four chips: the dp islands (sync-BN psum, dbias psum) ---------------------

@pytest.mark.parametrize("dtype", DTYPES)
def test_mesh_bn_act_compiles_on_four_chips(four_chips, dtype):
    spmd = FusedSpmd(mesh=four_chips)
    fn = lambda x, g, b: fused_bn_act(x, g, b, eps=1e-10, act="relu",
                                      interpret=False, spmd=spmd)
    text = _compile(fn, four_chips,
                    [((256, 56, 56, 192), dtype), ((192,), F32),
                     ((192,), F32)],
                    grad_argnums=(0, 1, 2),
                    specs=[P("data"), P(), P()])
    assert _kernels(text) >= 4          # sums+normalize, bwd sums+dx
    assert "all-reduce" in text         # the moment psum


@pytest.mark.parametrize("dtype", DTYPES)
def test_mesh_epilogue_and_pool_compile_on_four_chips(four_chips, dtype):
    spmd = FusedSpmd(mesh=four_chips)

    def fn(x, b):
        y = fused_bias_act(x, b, "relu", interpret=False, spmd=spmd)
        return fused_pool(y, 2, 2, 2, (0, 0), (0, 0), "max", False, False,
                          interpret=False, spmd=spmd)
    text = _compile(fn, four_chips, [((256, 56, 56, 96), dtype), ((96,), F32)],
                    grad_argnums=(0, 1), specs=[P("data"), P()])
    assert _kernels(text) >= 4
    assert "all-reduce" in text         # the dbias psum


# -- the kernels name themselves (PR 24) ---------------------------------------

def _kernel_scopes(text: str):
    """``[(instruction name, op_name)]`` of the compiled text's Pallas
    custom calls."""
    import re
    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line).group(1)
        op = re.search(r'op_name="([^"]*)"', line)
        out.append((name, op.group(1) if op else ""))
    return out


_KINDS = {
    "bn_act": (lambda x, g, b: fused_bn_act(x, g, b, eps=1e-10, act="relu",
                                            interpret=False),
               [((64, 14, 14, 128), BF16), ((128,), F32), ((128,), F32)],
               (0, 1, 2), {"bn_act_fwd", "bn_act_bwd"}),
    "pool": (lambda x: fused_pool(x, 2, 2, 2, (0, 0), (0, 0), "max", False,
                                  False, interpret=False),
             [((64, 28, 28, 128), BF16)], (0,),
             {"pool_fwd", "pool_bwd_max"}),
    "bias_act": (lambda x, b: fused_bias_act(x, b, "relu", interpret=False),
                 [((64, 13, 13, 384), BF16), ((384,), F32)], (0, 1),
                 {"bias_act_fwd", "bias_act_bwd"}),
    "lrn": (lambda x: fused_lrn(x, 5, 1e-4, 0.75, 1.0, interpret=False),
            [((64, 13, 13, 256), BF16)], (0,), {"lrn_fwd", "lrn_bwd"}),
    "stem": (lambda x, mean, f: fused_decode_normalize(
        x, mean, f, BF16, interpret=False),
        [((32, 64, 64, 3), jnp.uint8), ((64, 64, 3), F32), ((), F32)], (),
        {"stem_fwd"}),
    "sgd_apply": (lambda ws, gs, ms, lr, mom: fused_sgd_apply(
        ws, gs, ms, lr, mom, wd=1e-4, clip=0.0, nag=False, interpret=False),
        [[((3, 3, 64, 64), F32)]] * 3 + [((), F32), ((), F32)], (),
        {"sgd_apply_update"}),
}


@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_kernel_events_carry_their_kind(one_chip, kind):
    """What a device trace calls a Pallas kernel is its custom call's
    instruction name: ``pl.pallas_call(name="<kind>_<fwd|bwd|..>")``
    puts the kind there (it read ``jvp__`` / ``transpose_jvp__``
    before), forward and backward, and into the ``op_name``."""
    from cxxnet_tpu.telemetry.traceparse import classify as tp_classify
    fn, args, grad_argnums, want = _KINDS[kind]
    text = _compile(fn, one_chip, args, grad_argnums=grad_argnums)
    got = _kernel_scopes(text)
    assert got
    stems = set()
    for name, scope in got:
        stem = name.rsplit(".", 1)[0] if name[-1].isdigit() else name
        assert stem.startswith(kind + "_"), (name, scope)
        assert f"/{stem}/pallas_call" in scope, (name, scope)
        if kind != "sgd_apply":     # the optimizer binds that scope
            assert tp_classify(scope)[2] == kind, (name, scope)
        stems.add(stem)
    assert stems == want
