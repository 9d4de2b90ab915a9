"""What LFM2-8B-A1B forced, each against the plain reference
``benchmarks/references/lfm2_8b_a1b.py`` at a small size on the CPU,
seeded random weights: the gated short convolution (``shortconv``),
forward and every gradient, and its causality; the flash kernels under
the interpreter at a head of 64 with four query heads a key/value head,
against ``attention_reference``, and ``attn_impl = auto`` choosing them
there on a TPU; the conf writer's ``lfm2_moe`` family; the test that
ties a chip's share to the model — the four expert shares of a sigmoid
layer without a shared expert add up to the uncut layer; the whole toy
model over three Adam steps, under ``remat`` as without; and the built
cell's parameter count."""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.graph import LayerSpec
from cxxnet_tpu.layers import ApplyCtx, create_layer
from cxxnet_tpu.ops import attention as A

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(ROOT, "tests", "benchmarks", "data", "lfm2_toy")
CELL = os.path.join(ROOT, "benchmarks", "configs", "lfm2_8b_a1b")
E = 16


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return load(os.path.join(ROOT, "benchmarks", "references",
                             "lfm2_8b_a1b.py"), "bench_lfm2_ref_layers")


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


# -- the short convolution ------------------------------------------------------


def conv_layer(taps=3):
    return create_layer(LayerSpec("shortconv", "conv", [0], [1], [
        ("conv_L_cache", str(taps)), ("init_sigma", "0.3"),
        ("random_type", "gaussian")]), [])


def conv_run(layer, params, x):
    (y,), _ = layer.apply(params, {}, [x[:, :, None, :]], ApplyCtx(
        train=True, compute_dtype=jnp.float32))
    return y[:, :, 0, :]


@pytest.mark.parametrize("taps, positions", [(3, 11), (3, 2), (1, 7),
                                             (4, 9)])
def test_the_short_convolution_is_the_reference_s(ref, taps, positions):
    """Forward, and the gradient of every weight and of the input, of a
    seeded random function of the output, at lengths under, at and over
    the taps."""
    layer = conv_layer(taps)
    params = layer.init_params(jax.random.PRNGKey(taps), [(E, positions, 1)])
    assert {k: v["wmat"].shape for k, v in params.items()} == {
        "in_proj": (E, 3, E), "conv": (taps, E), "out_proj": (E, E)}
    rng = np.random.RandomState(positions)
    x = jnp.asarray(rng.randn(2, positions, E), jnp.float32)
    probe = jnp.asarray(rng.randn(2, positions, E), jnp.float32)
    c = {"conv_L_cache": taps}
    with jax.default_matmul_precision("highest"):
        close(conv_run(layer, params, x), ref.short_conv(params, x, c))
        ours = jax.grad(lambda p, a: jnp.sum(conv_run(layer, p, a) * probe),
                        argnums=(0, 1))(params, x)
        theirs = jax.grad(lambda p, a: jnp.sum(
            ref.short_conv(p, a, c) * probe), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        close(a, b, 5e-5)


def test_the_taps_read_the_positions_they_name(ref):
    """``v[t] = sum_i w[i] u[t - (L-1) + i]``: with one tap left on, the
    output at t moves with the input at t - (L-1) + i alone."""
    layer = conv_layer(3)
    params = layer.init_params(jax.random.PRNGKey(0), [(E, 8, 1)])
    x = jnp.asarray(np.random.RandomState(1).randn(1, 8, E), jnp.float32)
    for i in range(3):
        w = jnp.zeros((3, E)).at[i].set(1.0)
        p = dict(params, conv={"wmat": w})
        jac = jax.jacobian(lambda a: conv_run(layer, p, a)[0, 5])(x)
        moved = np.nonzero(np.abs(np.asarray(jac)).sum(axis=(0, 1, 3)))[0]
        assert set(moved) == {5, 5 - 2 + i}, (i, moved)


def test_the_short_convolution_is_causal():
    """Changing position t leaves every output before t as it was."""
    layer = conv_layer(3)
    params = layer.init_params(jax.random.PRNGKey(2), [(E, 12, 1)])
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 12, E), jnp.float32)
    y = np.asarray(conv_run(layer, params, x))
    for t in (0, 5, 11):
        moved = x.at[:, t].add(jnp.asarray(rng.randn(2, E), jnp.float32))
        y2 = np.asarray(conv_run(layer, params, moved))
        assert np.array_equal(y2[:, :t], y[:, :t]), t
        assert not np.allclose(y2[:, t], y[:, t]), t


def test_the_kind_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="conv_L_cache"):
        conv_layer(0)
    layer = conv_layer(3)
    params = layer.init_params(jax.random.PRNGKey(0), [(E, 4, 1)])
    with pytest.raises(ValueError, match="sequence-parallel"):
        layer.apply(params, {}, [jnp.zeros((1, 4, 1, E))], ApplyCtx(
            train=True, compute_dtype=jnp.float32, seq_axis="seq"))


# -- attention at a head of 64 --------------------------------------------------


@pytest.mark.parametrize("positions, block", [(256, 128), (384, 128)])
def test_the_flash_kernels_at_a_head_of_64(positions, block):
    """Four query heads a key/value head, heads of 64 (half the lanes):
    the kernels under the interpreter against ``attention_reference``,
    forward and the gradients of q, k and v."""
    rng = np.random.RandomState(positions)
    q = jnp.asarray(rng.randn(1, positions, 8, 64), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, positions, 2, 64), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(rng.randn(1, positions, 8, 64), jnp.float32)
    flash = lambda a, b, c: A.flash_attention(a, b, c, True, None, block,
                                              block, True)
    plain = lambda a, b, c: A.attention_reference(a, b, c, causal=True)
    with jax.default_matmul_precision("highest"):
        close(flash(q, k, v), plain(q, k, v), 1e-5)
        ours = jax.vjp(flash, q, k, v)[1](g)
        theirs = jax.vjp(plain, q, k, v)[1](g)
    for a, b in zip(ours, theirs):
        close(a, b, 1e-4)


def test_auto_takes_the_kernels_at_a_head_of_64_on_a_tpu(monkeypatch):
    """The cell's attention layer — 32 query heads of 64 over 8 — at 8192
    positions: ``attn_impl = auto`` is the kernel at blocks of 1024 on a
    TPU backend, and XLA's dots here."""
    layer = create_layer(LayerSpec("gqa", "b1_attn", [0], [1], [
        ("nhead", "32"), ("nkvhead", "8"), ("head_dim", "64"),
        ("qk_norm", "1"), ("rope_theta", "1000000")]), [])
    assert layer._impl(8192) == ("ref", 1024)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert layer._impl(8192) == ("flash", 1024)


# -- the conf writer ------------------------------------------------------------


def _tool():
    return load(os.path.join(ROOT, "tools", "gen_joyai_conf.py"),
                "gen_conf_for_lfm2_layers")


def _kinds(text):
    """``[(layer name, kind)]`` of a conf's layer lines, in order."""
    out = []
    for line in text.splitlines():
        if line.startswith("layer["):
            kind, name = line.split("= ", 1)[1].split(":")
            out.append((name, kind))
    return out


def test_the_writer_s_lfm2_family(ref):
    with open(CELL + ".json") as f:
        c = json.load(f)
    kinds = [k for name, k in _kinds(_tool().conf(c))
             if k not in ("rmsnorm", "add")]
    assert kinds == ["embed", "shortconv", "ffn", "gqa", "moe",
                     "shortconv", "moe", "shortconv", "moe", "shortconv",
                     "moe", "seqfc", "lmloss"]
    # kinds follow layer_types; the dense layers come first
    other = dict(c, layer_types=["full_attention", "conv", "conv",
                                 "full_attention", "conv"],
                 num_dense_layers=2)
    kinds = [(n, k) for n, k in _kinds(_tool().conf(other))
             if k in ("shortconv", "gqa", "ffn", "moe")]
    assert kinds == [("b0_attn", "gqa"), ("b0_ffn", "ffn"),
                     ("b1_conv", "shortconv"), ("b1_ffn", "ffn"),
                     ("b2_conv", "shortconv"), ("b2_moe", "moe"),
                     ("b3_attn", "gqa"), ("b3_moe", "moe"),
                     ("b4_conv", "shortconv"), ("b4_moe", "moe")]
    # the reference walks the same chain by the same names
    names = [n for n, k in kinds]
    walked = [s[0] for s in ref.stages(other)][1:-1]
    assert walked == names
    for bad in (dict(c, conv_bias=True), dict(c, use_expert_bias=False),
                dict(c, layer_types=c["layer_types"][:4]),
                dict(c, layer_types=["conv"] * 4 + ["sliding_attention"])):
        with pytest.raises(ValueError):
            _tool().conf(bad)


# -- the expert layer: a quarter of the experts on each chip --------------------


def moe_layer(first, held):
    return create_layer(LayerSpec("moe", "moe", [0], [1], [
        ("router", "sigmoid"), ("num_expert", "32"), ("topk", "4"),
        ("nhidden", "12"), ("shared_expert", "0"),
        ("routed_scaling_factor", "1"), ("expert_first", str(first)),
        ("expert_held", str(held)), ("init_sigma", "0.3"),
        ("random_type", "gaussian")]), [])


def test_the_four_expert_shares_add_up_to_the_uncut_layer(ref):
    """32 experts over 4 chips of 8 each — the deployment's way: every
    chip routes over all 32 under the same selection bias and computes
    its own experts' pairs; there is no shared expert, so the partial
    sums add up to the uncut layer as they stand. The program's gates
    differ from the reference's by its 1e-20 against the family's 1e-6
    in the denominator: under 1e-6 of themselves."""
    whole = moe_layer(0, 32)
    params = whole.init_params(jax.random.PRNGKey(7), [(E, 24, 1)])
    assert "shared" not in params
    rng = np.random.RandomState(7)
    bias = jnp.asarray(0.1 * rng.randn(32), jnp.float32)
    state = dict(whole.init_state([(E, 24, 1)]), sel_bias=bias)
    x = jnp.asarray(rng.randn(2, 24, E), jnp.float32)
    ctx = ApplyCtx(train=True, compute_dtype=jnp.float32)
    c = {"num_experts_per_tok": 4, "norm_topk_prob": True,
         "routed_scaling_factor": 1, "bias_update_rate": 0.001,
         "num_experts": 32, "expert_first": 0}
    total, held_pairs = 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for chip in range(4):
            share = dict(params, **{k: {"wmat": params[k]["wmat"][
                8 * chip:8 * chip + 8]} for k in "gho"})
            (y,), new = moe_layer(8 * chip, 8).apply(
                share, state, [x[:, :, None, :]], ctx)
            got = y[:, :, 0, :]
            want, new_bias = ref.experts(share, bias, x, dict(
                c, num_experts=8, expert_first=8 * chip))
            close(got, want)
            close(new["sel_bias"], new_bias, 1e-7)
            total = total + got
            held_pairs += float(new["stats"][0])
        assert held_pairs == 2 * 24 * 4     # every pair on exactly one chip
        close(total, ref.experts(params, bias, x, c)[0])
        # the bias chooses: left out, another set of experts is summed
        assert np.abs(np.asarray(total) - np.asarray(ref.experts(
            params, jnp.zeros(32), x, c)[0])).max() > 1e-3


# -- the whole toy model, and the built cell --------------------------------------


def _toy():
    with open(os.path.join(TOY, "configs", "lfm2_toy.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(TOY, "configs", "lfm2_toy.conf")) as f:
        return cfg, f.read()


def test_the_toy_model_trains_as_the_reference_does(ref):
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    cfg, text = _toy()
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
    zero = {n: np.zeros(16, np.float32) for n in ref.moe_names(cfg)}
    want = ref.train_steps(ref.Model(cfg), params0, zero,
                           toks.astype(np.int32), label.astype(np.int32),
                           cfg["train"]["eta"])
    assert got[2] < got[0]
    np.testing.assert_allclose(got, want, atol=5e-5)
    kinds = [layer.spec.type for layer in tr.net.layers]
    assert kinds.count("shortconv") == 2 and kinds.count("gqa") == 1
    assert kinds.count("ffn") == 1 and kinds.count("moe") == 2
    # the routers' bias moved by the rate a step, three steps
    steps = np.asarray(tr.net_state["b1_moe"]["sel_bias"]) \
        / cfg["bias_update_rate"]
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
    assert 0 < np.abs(steps).max() <= 3


def _toy_net(remat):
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.graph import build_graph
    from cxxnet_tpu.model import Network
    _, text = _toy()
    assert "remat = 1\n" in text
    cfg = parse_config_string(text.replace("remat = 1\n", f"remat = {remat}\n")
                              + "batch_size = 2\n")
    return Network(build_graph(cfg), cfg)


def test_remat_rebuilds_the_short_convolution_and_keeps_nothing_of_it():
    """Loss and gradients under ``remat = 1`` are those under ``remat =
    0``; the policy keeps the attention kernel's residuals and nothing of
    a ``shortconv`` layer but its input: its in-projection runs again in
    the backward (two layers: four in-projections, where ``remat = 0``
    has two)."""
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(0, 64, (2, 1, 1, 32)), jnp.float32)
    label = jnp.asarray(rng.randint(0, 64, (2, 32)), jnp.float32)
    out, counts = {}, {}
    for remat in (0, 1):
        net = _toy_net(remat)
        params, state = net.init(jax.random.PRNGKey(0))

        def loss(p):
            return net.apply(p, state, toks, label, None,
                             rng=jax.random.PRNGKey(1), train=True).loss
        out[remat] = jax.value_and_grad(loss)(params)
        text = str(jax.make_jaxpr(jax.grad(loss))(params))
        counts[remat] = len(re.findall(
            r"f32\[2,32,3,64\] = dot_general", text))
    assert counts == {0: 2, 1: 4}, counts
    close(out[1][0], out[0][0], 1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(out[1][1]),
                    jax.tree_util.tree_leaves(out[0][1])):
        close(a, b, 1e-5)


def test_the_cell_s_net_holds_541_374_592_parameters():
    """541 374 592 parameters (16 bytes each with Adam: 8.66 GB), from the
    conf's layer lines by shapes only: nothing is allocated."""
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.graph import build_graph
    from cxxnet_tpu.model import Network
    E_, V = 2048, 16384
    conv = 4 * E_ * E_ + 3 * E_
    attn = 2 * E_ * E_ + 2 * E_ * 512 + 2 * 64
    moe = E_ * 32 + 8 * 3 * E_ * 1792
    dense = 3 * E_ * 7168
    total = 2 * V * E_ + (conv + dense + 2 * E_) + (attn + moe + 2 * E_) \
        + 3 * (conv + moe + 2 * E_) + E_
    assert total == 541374592
    with open(CELL + ".conf") as f:
        cfg = parse_config_string(f.read() + "batch_size = 1\n")
    net = Network(build_graph(cfg), cfg)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes)) == total
