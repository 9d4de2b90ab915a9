"""The layer kinds Laguna-S-2.1 forced (PR 32), each against the plain
reference ``benchmarks/references/laguna_s_2_1.py`` at a small size on
the CPU, seeded random weights: ``gqa`` (grouped key/value heads, a
window, rotary on part of the head with a plain or YaRN table, the
per-head gate) through the Pallas kernel under the interpreter and
through XLA's dots, forward and gradients; the kernel over several
blocks and ``chunked_attention`` at the op, against a masked softmax
written here; YaRN's table against
hand-computed values; the softmax-scored no-drop ``moe`` against dense
experts under their gates; the whole toy model over three Adam steps;
and the test that ties a chip's share to the model: the head shares'
partial outputs add up to the uncut attention, the expert shares'
partial sums, the shared expert counted once, to the uncut layer."""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.graph import LayerSpec
from cxxnet_tpu.layers import ApplyCtx, create_layer
from cxxnet_tpu.ops.attention import (flash_tile_classes, flash_tiles, rope,
                                      rope_frequencies, rope_partial)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(ROOT, "tests", "benchmarks", "data", "laguna_toy")
E, D = 16, 8
ROPE = {"full_attention": {
            "rope_theta": 100, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
CONFIG = {"head_dim": D, "sliding_window": 7, "rope_parameters": ROPE,
          "hidden_size": E, "norm_topk_prob": True,
          "num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "bench_laguna_ref_layers", os.path.join(
            ROOT, "benchmarks", "references", "laguna_s_2_1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gqa_layer(kind, heads, kv_heads, **more):
    r = ROPE[kind]
    cfg = {"nhead": heads, "nkvhead": kv_heads, "head_dim": D,
           "window": CONFIG["sliding_window"]
           if kind == "sliding_attention" else 0, "head_gate": 1,
           "rotary_dim": int(D * r["partial_rotary_factor"]),
           "rope_theta": r["rope_theta"], "rope_type": r["rope_type"],
           "init_sigma": 0.3, "random_type": "gaussian"}
    if r["rope_type"] == "yarn":
        cfg.update(rope_factor=r["factor"], rope_beta_fast=r["beta_fast"],
                   rope_beta_slow=r["beta_slow"],
                   rope_original_max_position=r[
                       "original_max_position_embeddings"],
                   rope_attention_factor=r["attention_factor"])
    cfg.update(more)
    return create_layer(LayerSpec("gqa", "attn", [0], [1],
                                  [(k, str(v)) for k, v in cfg.items()]), [])


def run(layer, params, x, state=None):
    """The layer on (B, S, E) in float32 -> (B, S, E)."""
    ctx = ApplyCtx(train=True, compute_dtype=jnp.float32)
    (y,), new = layer.apply(params, state or {}, [x[:, :, None, :]], ctx)
    return y[:, :, 0, :], new


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


# -- gqa against the reference's attention --------------------------------------

CASES = [(kind, g, impl, S)
         for kind in ("full_attention", "sliding_attention")
         for g in (1, 6, 9)
         for impl, S in (("flash", 48), ("ref", 48), ("auto", 200))]


@pytest.mark.parametrize("kind, group, impl, positions", CASES)
def test_gqa_matches_the_reference_attention(ref, kind, group, impl,
                                             positions):
    """Under 128 positions the kernel takes the row as one block (the
    interpreter runs it here, a window of 7 masked inside the tile); no
    block divides 200, and ``auto`` takes XLA's dots there on a TPU as
    it does here. The kernel over several blocks is held at the op,
    below."""
    kv_heads = 2 if group == 1 else 1
    layer = gqa_layer(kind, group * kv_heads, kv_heads, attn_impl=impl)
    params = layer.init_params(jax.random.PRNGKey(group), [(E, positions, 1)])
    rng = np.random.RandomState(positions + group)
    x = jnp.asarray(rng.randn(2, positions, E), jnp.float32)
    w = jnp.asarray(rng.randn(2, positions, E), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda p, x_: jnp.sum(run(layer, p, x_)[0] * w),
            (0, 1))(params, x)
        want, want_g = jax.value_and_grad(
            lambda p, x_: jnp.sum(ref.attention(p, x_, CONFIG, kind) * w),
            (0, 1))(params, x)
    close(got, want)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        close(a, b)


def plain_attention(q, k, v, window):
    """The oracle of the op tests: a masked softmax a query head at a
    time, head ``h`` reading key/value head ``h // G``; nothing of
    ``ops/attention.py``."""
    S, H, G = q.shape[1], q.shape[2], q.shape[2] // k.shape[2]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    keep = (j <= i) if window is None else (j <= i) & (j > i - window)
    heads = []
    for h in range(H):
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // G]) \
            / math.sqrt(q.shape[-1])
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bqk,bkd->bqd", pr, v[:, :, h // G]))
    return jnp.stack(heads, axis=2)


def _held_to_the_oracle(fn, positions, group, window, seed):
    kv_heads = 2 if group == 1 else 1
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(2, positions, group * kv_heads, D), jnp.float32)
    k, v, w = (jnp.asarray(rng.randn(2, positions, n, D), jnp.float32)
               for n in (kv_heads, kv_heads, group * kv_heads))
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * w), (0, 1, 2))(q, k, v)
        want, want_g = jax.value_and_grad(
            lambda *a: jnp.sum(plain_attention(*a, window) * w),
            (0, 1, 2))(q, k, v)
    close(got, want)
    for a, b in zip(got_g, want_g):
        close(a, b)


@pytest.mark.parametrize("window", (None, 7, 16, 20))
@pytest.mark.parametrize("group", (1, 6, 9))
def test_the_kernel_over_several_blocks(group, window):
    """``flash_attention`` itself, 48 positions at blocks of 16 under the
    interpreter: three q-blocks, so a window of 7 or 16 leaves whole
    tiles out of the band (neither computed nor fetched) and one of 20
    cuts through a tile two blocks back; dk and dv of a key/value head
    are the sum over its ``group`` query heads."""
    from cxxnet_tpu.ops.attention import flash_attention
    _held_to_the_oracle(
        lambda q, k, v: flash_attention(q, k, v, True, None, 16, 16, None,
                                        window), 48, group, window, group)


@pytest.mark.parametrize("window", (None, 100))
def test_the_kernel_at_blocks_of_128(window):
    """384 positions at the smallest block the layer takes on a chip,
    6 query heads a key/value head, a band of 100: the first k-block is
    wholly outside the last q-block's band."""
    from cxxnet_tpu.ops.attention import flash_attention
    _held_to_the_oracle(
        lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128, None,
                                        window), 384, 6, window, 384)


@pytest.mark.parametrize("positions", (48, 40))
@pytest.mark.parametrize("window", (None, 7))
@pytest.mark.parametrize("group", (1, 6, 9))
def test_chunked_attention_reads_groups_and_a_window(group, window,
                                                     positions):
    """The XLA scan over key blocks of 16: 48 positions divide them, 40
    do not and the last block is padded."""
    from cxxnet_tpu.ops.attention import chunked_attention
    _held_to_the_oracle(
        lambda q, k, v: chunked_attention(q, k, v, causal=True, block_k=16,
                                          window=window),
        positions, group, window, positions + group)


def test_the_kernel_refuses_positions_no_block_divides():
    layer = gqa_layer("sliding_attention", 6, 1, attn_impl="flash")
    params = layer.init_params(jax.random.PRNGKey(0), [(E, 200, 1)])
    with pytest.raises(ValueError, match="no flash block"):
        run(layer, params, jnp.zeros((1, 200, E)))
    with pytest.raises(ValueError, match="unknown gqa attn_impl"):
        gqa_layer("full_attention", 6, 1, attn_impl="chunked")


def test_the_selection_log_and_the_tile_gauges():
    from cxxnet_tpu.ops.fused import selection_counts, selection_site
    from cxxnet_tpu.telemetry.registry import get_registry
    log = {}
    for name, kind, impl in (("a", "full_attention", "flash"),
                             ("b", "sliding_attention", "flash"),
                             ("c", "sliding_attention", "auto")):
        layer = gqa_layer(kind, 6, 1, attn_impl=impl)
        layer.name = name
        layer.infer_shapes([(E, 384, 1)])
        params = layer.init_params(jax.random.PRNGKey(0), [(E, 384, 1)])
        with selection_site(log, name):
            run(layer, params, jnp.zeros((1, 384, E)))
    assert dict(selection_counts(log)["attention"]) == {
        "gqa.flash": 1, "gqa.flash_window": 1, "gqa.ref": 1}
    # 384 positions take blocks of 128: 6 of 9 tiles causal; a window of
    # 7 touches the diagonal's tile and the one before it
    tiles = {labels: child.value for what in ("executed", "total")
             for labels, child in get_registry().get(
                 "cxxnet_attn_tiles_" + what).samples()
             for labels in [(what,) + tuple(labels)]}
    assert tiles[("executed", "a")] == 6 and tiles[("total", "a")] == 9
    assert tiles[("executed", "b")] == 5 and tiles[("total", "b")] == 9
    # a block of 128 is its own one sub-tile: the diagonal's three tiles
    # are masked whole, the three below it run without a mask; under the
    # window every executed tile has an edge. The pairs multiplied are
    # then the executed tiles', over the pairs the mask keeps
    read = lambda what, layer: dict(
        (tuple(labels), child.value) for labels, child in
        get_registry().get("cxxnet_attn_" + what).samples())[(layer,)]
    assert read("tiles_masked", "a") == 3 and read("tiles_masked", "b") == 5
    assert read("subtile", "a") == read("subtile", "b") == 128
    assert read("pairs_multiplied_over_attended", "a") == pytest.approx(
        6 * 128 ** 2 / (384 * 385 // 2))
    assert read("pairs_multiplied_over_attended", "b") == pytest.approx(
        5 * 128 ** 2 / (7 * 8 // 2 + (384 - 7) * 7))
    for layer, window in (("a", None), ("b", 7)):
        cls = flash_tile_classes(384, 128, window)
        assert read("tiles_masked", layer) == cls["edge"]
        assert read("tiles_executed", layer) == cls["interior"] + cls["edge"]
    assert flash_tiles(8192, 1024) == (36, 64)
    assert flash_tiles(8192, 512, 512) == (31, 256)


# -- rotary -------------------------------------------------------------------


def test_the_yarn_table_against_hand_computed_values():
    """Laguna-S-2.1's full layers: 64 rotated features at theta 500 000,
    factor 128 over 8192 original positions, beta 32 / 1."""
    freqs, mscale = rope_frequencies(
        64, 500000.0, (128.0, 8192.0, 32.0, 1.0, 1.4852030263919618))
    assert mscale == 1.4852030263919618 and len(freqs) == 32
    dim = lambda n: 64 * math.log(8192 / (2 * math.pi * n)) \
        / (2 * math.log(500000.0))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (9, 18)
    f = lambda i: 500000.0 ** (-2 * i / 64)
    assert freqs[0] == 1.0
    for i in range(10):                     # below the ramp: untouched
        assert freqs[i] == pytest.approx(f(i), rel=1e-12)
    for i in range(18, 32):                 # past it: interpolated
        assert freqs[i] == pytest.approx(f(i) / 128, rel=1e-12)
    # on it: pair 13 is 4/9 of the way
    assert freqs[13] == pytest.approx(f(13) * (5 / 9) + f(13) / 128 * (4 / 9),
                                      rel=1e-12)
    assert freqs[13] == pytest.approx(0.0027054, rel=1e-4)
    # the plain table, and the whole head at factor 1, is rope()
    plain, one = rope_frequencies(8, 10000.0)
    assert one == 1.0 and plain == tuple(
        10000.0 ** (-i / 4) for i in range(4))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 12, 3, 8), jnp.float32)
    close(rope_partial(x, plain), rope(x, 10000.0), 1e-6)
    # half the head rotated: the other half passes through
    half, _ = rope_frequencies(4, 100.0)
    y = rope_partial(x, half, 1.5)
    assert np.array_equal(np.asarray(y[..., 4:]), np.asarray(x[..., 4:]))
    ang = 5 * np.asarray(half)
    close(y[0, 5, 0, :2], 1.5 * (np.asarray(x[0, 5, 0, :2]) * np.cos(ang)
                                 - np.asarray(x[0, 5, 0, 2:4]) * np.sin(ang)),
          1e-6)


# -- the softmax-scored no-drop layer ---------------------------------------------


def moe_layer(first, held, all_experts=16):
    cfg = {"router": "softmax_nodrop", "num_expert": all_experts, "topk": 3,
           "nhidden": 12, "shared_expert": 1, "routed_scaling_factor": 2.5,
           "expert_first": first, "expert_held": held, "init_sigma": 0.3,
           "random_type": "gaussian"}
    return create_layer(LayerSpec("moe", "moe", [0], [1],
                                  [(k, str(v)) for k, v in cfg.items()]), [])


def moe_config(first, held):
    return dict(CONFIG, num_experts=held, expert_first=first)


@pytest.mark.parametrize("first, held", [(4, 4), (0, 16), (13, 3)])
def test_softmax_nodrop_matches_dense_experts_under_their_gates(
        ref, first, held):
    layer = moe_layer(first, held)
    params = layer.init_params(jax.random.PRNGKey(3), [(E, 24, 1)])
    state = layer.init_state([(E, 24, 1)])
    assert set(state) == {"stats"}          # no selection bias to carry
    rng = np.random.RandomState(first)
    x = jnp.asarray(rng.randn(2, 24, E), jnp.float32)
    w = jnp.asarray(rng.randn(2, 24, E), jnp.float32)
    c = moe_config(first, held)
    with jax.default_matmul_precision("highest"):
        (got, new), got_g = jax.value_and_grad(
            lambda p, x_: (lambda y, st: (jnp.sum(y * w), st))(
                *run(layer, p, x_, state)), (0, 1), has_aux=True)(params, x)
        want, want_g = jax.value_and_grad(
            lambda p, x_: jnp.sum(ref.experts(p, x_, c) * w),
            (0, 1))(params, x)
    close(got, want)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        close(a, b)
    stats = np.asarray(new["stats"])
    assert stats[0] + stats[1] == 2 * 24 * 3 and stats[2] == 0
    assert stats[4] == 0                    # no bias: its largest is 0
    if held == 16:
        assert stats[1] == 0


def test_the_capacity_router_keeps_its_spelling():
    with pytest.raises(ValueError, match="1 or 2"):
        create_layer(LayerSpec("moe", "m", [0], [1], [
            ("router", "softmax"), ("topk", "10")]), [])
    with pytest.raises(ValueError, match="unknown moe router"):
        create_layer(LayerSpec("moe", "m", [0], [1], [
            ("router", "softmax_topk")]), [])


# -- the shares add up to the model ------------------------------------------------


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_the_head_shares_add_up_to_the_uncut_attention(ref, kind):
    """8 query heads over 4 key/value heads, cut over a pair of chips as
    the deployment cuts them: each holds 2 key/value heads with their 4
    query heads, and W_o gives the held heads' partial sum."""
    whole = gqa_layer(kind, 8, 4)
    params = whole.init_params(jax.random.PRNGKey(5), [(E, 32, 1)])
    x = jnp.asarray(np.random.RandomState(5).randn(2, 32, E), jnp.float32)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for chip in range(2):
            q, kv = slice(4 * chip, 4 * chip + 4), slice(2 * chip, 2 * chip + 2)
            share = {"q": {"wmat": params["q"]["wmat"][:, q]},
                     "k": {"wmat": params["k"]["wmat"][:, kv]},
                     "v": {"wmat": params["v"]["wmat"][:, kv]},
                     "gate": {"wmat": params["gate"]["wmat"][:, q]},
                     "o": {"wmat": params["o"]["wmat"][q]}}
            total = total + run(gqa_layer(kind, 4, 2), share, x)[0]
        close(total, ref.attention(params, x, CONFIG, kind))


def test_the_expert_shares_add_up_to_the_uncut_layer(ref):
    """16 experts over 4 chips of 4: every chip routes over all 16,
    computes its own experts' pairs and the shared expert; the partial
    sums, the shared expert counted once, are the uncut layer."""
    whole = moe_layer(0, 16)
    params = whole.init_params(jax.random.PRNGKey(7), [(E, 24, 1)])
    state = whole.init_state([(E, 24, 1)])
    x = jnp.asarray(np.random.RandomState(7).randn(2, 24, E), jnp.float32)
    total, held_pairs = 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(x, params["shared"])
        for chip in range(4):
            rows = slice(4 * chip, 4 * chip + 4)
            share = dict(params, **{k: {"wmat": params[k]["wmat"][rows]}
                                    for k in "gho"})
            y, new = run(moe_layer(4 * chip, 4), share, x, state)
            total = total + (y - shared)
            held_pairs += float(new["stats"][0])
        assert held_pairs == 2 * 24 * 3     # every pair on exactly one chip
        close(total + shared, ref.experts(params, x, moe_config(0, 16)))


# -- the whole toy model ---------------------------------------------------------------


def test_the_toy_model_trains_as_the_reference_does(ref):
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    with open(os.path.join(TOY, "configs", "laguna_toy.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(TOY, "configs", "laguna_toy.conf")) as f:
        text = f.read()
    rows, S, V = 2, cfg["positions"], cfg["vocab_size"]
    tr = Trainer(parse_config_string(
        text + f"dev = cpu:0\nseed = 5\nbatch_size = {rows}\n"))
    tr.init_model()
    rng = np.random.RandomState(5)
    toks = rng.randint(0, V, (rows, S))
    label = (toks + toks[:, :1]) % V
    batch = DataBatch(data=toks.astype(np.float32).reshape(rows, 1, 1, S),
                      label=label.astype(np.float32))
    params0 = ref.initial_params(tr, 5)
    got = []
    for _ in range(3):
        tr.update(batch)
        got.append(float(tr.last_loss))
    want = ref.train_steps(ref.Model(cfg), params0, toks.astype(np.int32),
                           label.astype(np.int32), cfg["train"]["eta"])
    assert got[2] < got[0]
    np.testing.assert_allclose(got, want, atol=5e-5)
    # the net's kinds: full and window attention, the no-drop experts
    kinds = [layer.spec.type for layer in tr.net.layers]
    assert kinds.count("gqa") == 3 and kinds.count("moe") == 2
    assert [layer.window for layer in tr.net.layers
            if layer.spec.type == "gqa"] == [0, 8, 0]


# -- remat = 1 keeps the kernel's residuals for the new kind too ---------------------


def _toy_net(remat):
    """The toy ``laguna_s_2_1`` net with its attention on the Pallas
    kernel (32 positions: one block)."""
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.graph import build_graph
    from cxxnet_tpu.model import Network
    with open(os.path.join(TOY, "configs", "laguna_toy.conf")) as f:
        text = f.read()
    assert "remat = 1\n" in text and text.count("= gqa:") == 3
    text = text.replace("remat = 1\n", f"remat = {remat}\n").replace(
        "  head_gate = 1\n", "  head_gate = 1\n  attn_impl = flash\n")
    cfg = parse_config_string(text + "batch_size = 2\n")
    return Network(build_graph(cfg), cfg)


def test_toy_net_under_remat_is_the_plain_net_and_runs_each_kernel_once():
    """Loss and gradients under ``remat = 1`` are those under ``remat =
    0``, and the rebuilt layers hold no second run of the kernel's
    forward: three ``gqa`` layers, three forward and three backward
    kernels (``model.py`` keeps ``FLASH_RESIDUALS`` whatever the
    layer's kind)."""
    plain, remat = _toy_net(0), _toy_net(1)
    params, state = plain.init(jax.random.PRNGKey(3))
    toks = np.random.RandomState(3).randint(0, 64, (2, 32))
    data = jnp.asarray(toks.reshape(2, 1, 1, 32), jnp.float32)
    label = jnp.asarray((toks + toks[:, :1]) % 64, jnp.float32)
    loss = lambda net: lambda p: net.apply(
        p, state, data, label=label, mask=jnp.ones((2,)), train=True).loss
    l0, g0 = jax.value_and_grad(loss(plain))(params)
    l1, g1 = jax.value_and_grad(loss(remat))(params)
    close(l1, l0, 1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g0)):
        close(a, b, 1e-5)
    text = str(jax.make_jaxpr(jax.grad(loss(remat)))(params))
    assert (text.count("name=flash_fwd"), text.count("name=flash_bwd")) \
        == (3, 3)
