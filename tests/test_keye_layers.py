"""What Keye-VL-2.0's language model forced (PR 34), each against the plain
reference ``benchmarks/references/keye_vl_2_0_30b_a3b.py`` at a small
size on the CPU, seeded random weights: the indexer's score and the exact
selection (lengths under, at and over ``topk``; a planted tie); sparse
``gqa`` — the kind ``dsa`` — through the Pallas kernels under the
interpreter and through XLA's dots, forward and gradients, at lengths
that do and do not divide the block; the selection operand at the ops;
q/k norm; the three-section rotary at grid positions against hand-computed
angles; the indexer's loss and where gradients may flow, its gradients
made in the forward pass against plain autodiff, and one score kernel
and one head sum a layer under ``remat``; that ``gqa``
without the new keys traces what it traced; the whole toy model over
three Adam steps; and the test that ties a chip's share to the model: the
sixteen expert shares' partial sums add up to the uncut expert layer."""

import importlib.util
import json
import math
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
TOY = os.path.join(ROOT, "tests", "benchmarks", "data", "keye_toy")
E, D, J, DI = 16, 8, 3, 4
CONFIG = {"head_dim": D, "hidden_size": E, "rms_norm_eps": 1e-6,
          "rope_theta": 100, "norm_topk_prob": True,
          "num_experts_per_tok": 3,
          "rope_scaling": {"mrope_section": [1, 2, 1]},
          "sa_config": {"indexer_head_dim": DI, "indexer_num_heads": J,
                        "topk": 8}}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "bench_keye_ref_layers", os.path.join(
            ROOT, "benchmarks", "references", "keye_vl_2_0_30b_a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(topk=8):
    return dict(CONFIG, sa_config=dict(CONFIG["sa_config"], topk=topk))


def dsa_layer(heads=4, kv_heads=2, topk=8, kind="dsa", inputs=(0,), **more):
    cfg = {"nhead": heads, "nkvhead": kv_heads, "head_dim": D,
           "qk_norm": 1, "rope_theta": CONFIG["rope_theta"],
           "mrope_section": "1,2,1", "index_heads": J,
           "index_head_dim": DI, "index_topk": topk,
           "init_sigma": 0.3, "random_type": "gaussian"}
    cfg.update(more)
    return create_layer(LayerSpec(kind, "attn", list(inputs), [9],
                                  [(k, str(v)) for k, v in cfg.items()]), [])


def weights(layer, positions, seed=0):
    """Seeded weights with the norms' gains and the LayerNorm's bias off
    their initial ones and zeros, so that a gain that is not applied
    shows."""
    p = layer.init_params(jax.random.PRNGKey(seed), [(E, positions, 1)])
    rng = np.random.RandomState(seed)
    for name in ("qnorm", "knorm", "iknorm"):
        if name in p:
            p[name] = {k: v + jnp.asarray(0.3 * rng.randn(*v.shape),
                                          jnp.float32)
                       for k, v in p[name].items()}
    return p


def run(layer, params, x, pos=None, train=True):
    """The layer on (B, S, E) in float32 -> ((B, S, E), new state)."""
    ctx = ApplyCtx(train=train, compute_dtype=jnp.float32)
    inputs = [x[:, :, None, :]]
    if pos is not None:           # (B, 3, S) -> the node's (B, S, 1, 3)
        inputs.append(jnp.transpose(pos, (0, 2, 1))[:, :, None, :])
    state = layer.init_state([(E, x.shape[1], 1)])
    (y,), new = layer.apply(params, state, inputs, ctx)
    return y[:, :, 0, :], new


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


def data(positions, seed, rows=2):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(rows, positions, E), jnp.float32),
            jnp.asarray(rng.randn(rows, positions, E), jnp.float32))


# -- the indexer's score and the selection ---------------------------------------


@pytest.mark.parametrize("positions, topk", [(6, 8), (8, 8), (24, 8),
                                             (200, 16)])
def test_the_score_and_the_selection_match_the_reference(ref, positions,
                                                         topk):
    """Lengths under, at and over ``topk``: while ``t < topk`` every
    causal key is kept."""
    layer = dsa_layer(topk=topk)
    params = weights(layer, positions, 3)
    x, _ = data(positions, positions)
    c = config(topk)
    with jax.default_matmul_precision("highest"):
        got_scores = layer._index(params, x, None, jnp.float32)
        want_scores = ref.index_scores(
            params, x, c, ref.text_positions(2, positions))
        want = ref.selection(want_scores, topk)
    close(got_scores, want_scores)
    got = np.asarray(layer.select(params, x)) != 0
    assert got.sum() == 2 * ref.selected_pairs(topk, positions)
    assert (got == np.asarray(want)).all()


def test_a_tie_goes_to_the_lower_position(ref):
    """Scores rounded to whole numbers tie by the dozen; ``lax.top_k``
    takes the lower index first, and so does the counting selection."""
    rng = np.random.RandomState(1)
    scores = jnp.asarray(np.round(2 * rng.randn(2, 64, 64)), jnp.float32)
    got = np.asarray(A.select_topk(scores, 8))
    assert (got == np.asarray(ref.selection(scores, 8))).all()
    assert got.sum() == 2 * ref.selected_pairs(8, 64)
    # one row by hand: keys 0..9 of query 9 score 1 but key 7, which
    # scores 2: key 7 and the seven lowest of the others
    row = jnp.ones((1, 10, 10)).at[0, 9, 7].set(2.0)
    assert list(np.nonzero(np.asarray(A.select_topk(row, 8))[0, 9])[0]) \
        == [0, 1, 2, 3, 4, 5, 6, 7]


@pytest.mark.parametrize("positions, block, dtype", [
    (256, 128, "float32"), (96, 96, "float32"),
    # more than two blocks a side: an interior tile, a diagonal tile and
    # a skipped tile in every row of tiles but the first and the last
    (384, 128, "float32"), (384, 128, "bfloat16"),
    # blocks of 1024 take a diagonal tile by sub-tiles of 256
    (2048, 1024, "float32")])
def test_the_score_kernel_is_the_jnp_form(positions, block, dtype):
    """Forward and the backward kernel's three gradients against
    ``index_scores_reference``'s. The cotangent handed to the kernel is
    NOT zero above the diagonal: the gradients may not see it (the
    reference gets the causal part alone)."""
    rng = np.random.RandomState(2)
    rows = 1 if positions > 1024 else 2
    qi = jnp.asarray(rng.randn(rows, positions, J, DI), dtype)
    ki = jnp.asarray(rng.randn(rows, positions, DI), dtype)
    w = jnp.asarray(rng.randn(rows, positions, J), jnp.float32)
    causal = np.tril(np.ones((positions, positions), bool))
    # bf16 operands: the kernel rounds a_j to bf16 before its two
    # products and its dq and dk on the way out, the reference neither
    tol = 2e-5 if dtype == "float32" else 2e-2
    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(A.index_scores_reference, qi, ki, w)
        got, vjp_k = jax.vjp(
            lambda *a: A.index_scores(*a, block, True), qi, ki, w)
        assert np.abs(np.where(causal, got - want, 0)).max() < 1e-4
        g = jnp.asarray(rng.randn(rows, positions, positions), jnp.float32)
        for a, b in zip(vjp_k(g), vjp(jnp.where(causal, g, 0.0))):
            assert a.dtype == b.dtype and a.shape == b.shape
            close(a, b, tol)
        close(A.index_scores_reference(qi, ki, w, chunk=32), want, 1e-5)


# -- the selection operand at the ops -------------------------------------------------


def plain_attention(q, k, v, keep):
    """The oracle: a masked softmax a query head at a time over the kept
    pairs; nothing of ``ops/attention.py``. -> (output, head-summed
    distribution)."""
    H, G = q.shape[2], q.shape[2] // k.shape[2]
    heads, total = [], 0.0
    for h in range(H):
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // G]) \
            / math.sqrt(q.shape[-1])
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bqk,bkd->bqd", pr, v[:, :, h // G]))
        total = total + pr
    return jnp.stack(heads, axis=2), total / H


def _operands(positions, group, seed, topk):
    kv_heads = 2 if group == 1 else 1
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(2, positions, group * kv_heads, D),
                    jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, positions, kv_heads, D), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    if topk:
        keep = A.select_topk(jnp.asarray(
            rng.randn(2, positions, positions), jnp.float32), topk)
    else:       # what a trained indexer tends to: two sinks and the last two
        t, s_ = np.arange(positions)[:, None], np.arange(positions)[None]
        keep = jnp.asarray(np.broadcast_to(
            (s_ <= t) & ((s_ < 2) | (s_ > t - 2)),
            (2, positions, positions)))
    return q, k, v, w, keep


OPS = {
    "reference": lambda q, k, v, keep: A.attention_reference(
        q, k, v, causal=True, select=keep),
    "chunked": lambda q, k, v, keep: A.chunked_attention(
        q, k, v, causal=True, block_k=16, select=keep),
    "kernel16": lambda q, k, v, keep: A.flash_attention_select(
        q, k, v, keep.astype(jnp.int8), None, 16, 16, True)[0],
    "kernel128": lambda q, k, v, keep: A.flash_attention_select(
        q, k, v, keep.astype(jnp.int8), None, 128, 128, True)[0],
}


@pytest.mark.parametrize("group", (1, 4))
@pytest.mark.parametrize("op, positions, topk", [
    ("reference", 40, 8), ("chunked", 48, 8), ("chunked", 40, 8),
    ("kernel16", 48, 8), ("kernel16", 64, 0), ("kernel128", 256, 48),
    ("kernel128", 512, 0)])
def test_the_ops_attend_the_selected_pairs_alone(op, positions, topk,
                                                 group):
    """Forward and the three gradients; 40 positions do not divide the
    chunked form's block of 16; ``topk`` 0 is a set of two sinks and the
    last two keys, which leaves tiles without a selected pair between the
    first column and the diagonal: the kernels skip them."""
    q, k, v, w, keep = _operands(positions, group, positions + group, topk)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda *a: jnp.sum(OPS[op](*a, keep) * w), (0, 1, 2))(q, k, v)
        want, want_g = jax.value_and_grad(
            lambda *a: jnp.sum(plain_attention(*a, keep)[0] * w),
            (0, 1, 2))(q, k, v)
    close(got, want)
    for a, b in zip(got_g, want_g):
        close(a, b)


def test_the_tiles_table_and_the_head_sum():
    """Blocks of 16 over 64 positions, two sinks and the last two keys:
    the table knows the tiles without a pair (class 0: all but the first
    column, the diagonal and the tile before it), a tile wholly selected (2), and what a
    skipped cell fetches; the head-summed distribution from the kernel's
    logsumexp is the oracle's."""
    q, k, v, _, keep = _operands(64, 4, 5, 0)
    sel = keep.astype(jnp.int8)
    cls, fetch = (np.asarray(a).reshape(2, 4, 4)
                  for a in A.select_tiles(sel, 16, 16))
    n = np.asarray(keep).reshape(2, 4, 16, 4, 16).sum((2, 4))
    assert ((cls == 0) == (n == 0)).all() and (cls[:, 0, 1:] == 0).all()
    assert ((cls == 2) == (n == 256)).all()
    # a block's first query keeps the key before it, in the block before
    assert (cls[0] > 0).tolist() == [[True, False, False, False],
                                     [True, True, False, False],
                                     [True, True, True, False],
                                     [True, False, True, True]]
    for b in range(2):
        for i in range(4):
            run_, last = [j for j in range(4) if cls[b, i, j]], None
            for j in range(4):
                last = j if cls[b, i, j] else last
                assert fetch[b, i, j] == (run_[0] if last is None else last)
    whole = np.asarray(A.select_tiles(jnp.ones((1, 32, 32), jnp.int8),
                                      16, 16)[0])
    assert (whole == 2).all()
    with jax.default_matmul_precision("highest"):
        _, lse = A.flash_attention_select(q, k, v, sel, None, 16, 16, True)
        want = plain_attention(q, k, v, keep)[1]
        close(A.head_sum_probs(q, k, lse, sel, None, 16, True), want)
        close(A.head_sum_probs_reference(q, k, sel), want)
    assert np.abs(np.asarray(want).sum(-1) - 1).max() < 1e-5


# -- the layer against the reference's attention -----------------------------------------


@pytest.mark.parametrize("impl, positions, topk", [
    ("ref", 40, 8), ("flash", 48, 8), ("flash", 256, 24), ("auto", 200, 16)])
@pytest.mark.parametrize("group", (2, 8))
def test_dsa_matches_the_reference_attention(ref, impl, positions, topk,
                                             group):
    """Output, the indexer's loss and every gradient of ``sum(y w) +
    L_I``. 256 positions are two blocks of 128 under the interpreter; no
    block divides 200, where ``auto`` takes XLA's dots on a TPU as it
    does here."""
    layer = dsa_layer(heads=group, kv_heads=1 if group == 8 else 2,
                      topk=topk, attn_impl=impl)
    params = weights(layer, positions, group)
    x, w = data(positions, positions + group)
    c = config(topk)

    def ours(p, x_):
        y, new = run(layer, p, x_)
        return jnp.sum(y * w) + new["_aux_loss"], (y, new)

    def theirs(p, x_):
        y, loss = ref.attention(p, x_, c, ref.text_positions(2, positions))
        return jnp.sum(y * w) + loss, (y, loss)
    with jax.default_matmul_precision("highest"):
        (_, (got, new)), got_g = jax.value_and_grad(
            ours, (0, 1), has_aux=True)(params, x)
        (_, (want, loss)), want_g = jax.value_and_grad(
            theirs, (0, 1), has_aux=True)(params, x)
    close(got, want)
    close(new["dsa_stats"][1], loss)
    assert float(new["dsa_stats"][0]) \
        == 2 * ref.selected_pairs(topk, positions)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        close(a, b)


def test_the_qk_norm_is_applied_and_can_be_left_out(ref):
    """With the gains off one, the layer is the reference; the reference
    with the norm left out (its control) is another function."""
    layer = dsa_layer()
    params = weights(layer, 24, 4)
    x, _ = data(24, 4)
    with jax.default_matmul_precision("highest"):
        got = run(layer, params, x)[0]
        want = ref.attention(params, x, config(),
                             ref.text_positions(2, 24))[0]
        ref.VARIANT = "no_qk_norm"
        try:
            without = ref.attention(params, x, config(),
                                    ref.text_positions(2, 24))[0]
        finally:
            ref.VARIANT = None
    close(got, want)
    assert np.abs(np.asarray(got - without)).max() > 1e-2
    plain = create_layer(LayerSpec("gqa", "attn", [0], [1], [
        ("nhead", "4"), ("nkvhead", "2"), ("head_dim", str(D)),
        ("qk_norm", "1")]), [])
    assert set(plain.init_params(jax.random.PRNGKey(0), [(E, 8, 1)])) \
        == {"q", "k", "v", "o", "qnorm", "knorm"}


# -- the three-section rotary ---------------------------------------------------------------


def test_the_rotary_reads_three_position_rows():
    """Four pairs in sections 1, 2, 1 at theta 100: pair i of the head's
    8 features turns by pos[c(i)] * 100^(-2i/8), c = 0, 1, 1, 2."""
    freqs = A.rope_frequencies(8, 100.0)[0]
    assert np.allclose(freqs, [1.0, 100 ** -0.25, 100 ** -0.5,
                               100 ** -0.75])
    pos = jnp.asarray([[[3, 5], [7, 2], [1, 9]]], jnp.float32)  # (1, 3, 2)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 2, 1, 8), jnp.float32)
    got = np.asarray(A.rope_sections(x, freqs, pos, (1, 2, 1)))
    for s, (t, h, w) in enumerate(((3, 7, 1), (5, 2, 9))):
        ang = np.array([t * 1.0, h * 100 ** -0.25, h * 100 ** -0.5,
                        w * 100 ** -0.75])
        a, b = np.asarray(x)[0, s, 0, :4], np.asarray(x)[0, s, 0, 4:]
        want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               a * np.sin(ang) + b * np.cos(ang)])
        assert np.abs(got[0, s, 0] - want).max() < 1e-5
    # text: three equal rows are the plain rotary
    text = jnp.broadcast_to(jnp.arange(2, dtype=jnp.float32), (1, 3, 2))
    close(A.rope_sections(x, freqs, text, (1, 2, 1)),
          A.rope_partial(x, freqs))
    with pytest.raises(ValueError, match="sections"):
        A.rope_sections(x, freqs, pos, (2, 2, 1))


def test_the_layer_takes_grid_positions_from_a_second_input(ref):
    """A (3,S,1) node bound as the layer's second input reaches both the
    main rotary (by section) and the indexer's (the temporal row); with
    none bound the rows are the token's index."""
    layer = dsa_layer(inputs=(0, 1))
    assert layer.infer_shapes([(E, 24, 1), (3, 24, 1)]) == [(E, 24, 1)]
    with pytest.raises(ValueError, match="positions"):
        layer.infer_shapes([(E, 24, 1), (2, 24, 1)])
    params = weights(layer, 24, 6)
    x, _ = data(24, 6)
    rng = np.random.RandomState(6)
    pos = np.stack([np.sort(rng.randint(0, 40, (2, 24)), axis=1)] +
                   [rng.randint(0, 12, (2, 24)) for _ in range(2)], axis=1)
    with jax.default_matmul_precision("highest"):
        got, new = run(layer, params, x, jnp.asarray(pos, jnp.float32))
        want, loss = ref.attention(params, x, config(), pos)
        text = run(layer, params, x)[0]
        close(text, ref.attention(params, x, config(),
                                  ref.text_positions(2, 24))[0])
    close(got, want)
    close(new["dsa_stats"][1], loss)
    assert np.abs(np.asarray(got - text)).max() > 1e-2


# -- where gradients may flow -------------------------------------------------------------


def test_the_indexer_learns_from_its_loss_alone():
    """The trunk's leaves' gradients are the same with ``index_loss_coef``
    0 and 1; the indexer's leaves' are zero at 0 and not at 1; the
    layer's input gets none from the loss. Where the indexer learns,
    the backward hands the input's gradient and the indexer's leaves' on
    together (one ``optimization_barrier``: the chip's scheduler may not
    put a layer's indexer off to the step's end); where it does not,
    there is nothing to tie."""
    x, w = data(24, 8)
    grads = {}
    for coef in (0, 1):
        layer = dsa_layer(index_loss_coef=coef)
        params = weights(layer, 24, 8)

        def f(p, x_):
            y, new = run(layer, p, x_)
            return jnp.sum(y * w) + new["_aux_loss"]
        grads[coef] = jax.grad(f, (0, 1))(params, x)
        assert str(jax.make_jaxpr(jax.grad(f, (0, 1)))(params, x)).count(
            "optimization_barrier") == coef
    for name in ("q", "k", "v", "o", "qnorm", "knorm"):
        for a, b in zip(jax.tree_util.tree_leaves(grads[0][0][name]),
                        jax.tree_util.tree_leaves(grads[1][0][name])):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
    assert np.array_equal(np.asarray(grads[0][1]), np.asarray(grads[1][1]))
    for name in ("iq", "ik", "iknorm", "iw"):
        for a, b in zip(jax.tree_util.tree_leaves(grads[0][0][name]),
                        jax.tree_util.tree_leaves(grads[1][0][name])):
            assert not np.asarray(a).any(), name
            assert np.abs(np.asarray(b)).max() > 0, name


def _plain_index_loss(scores, select, probs):
    """The indexer's loss as plain autodiff differentiates it: ``mean_t
    KL(p[t] || softmax of I[t] over the selected set)``."""
    keep = select != 0
    logz = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1,
                            keepdims=True)
    some = keep & (probs > 0)
    logp = jnp.log(jnp.where(some, probs, 1.0))
    return jnp.mean(jnp.sum(jnp.where(
        some, probs * (logp - (scores - logz)), 0.0), axis=-1))


def _plain_index_learned(layer):
    """``seq._index_learned`` as plain autodiff through the loss: the
    scores made again from the leaves, over the same target and set, and
    the backward left to differentiate them (a cotangent reaches the
    leaves through every step of the loss, not as a scale at its end)."""
    def learned(run, leaves, operands):
        x, pos, _, _, q, k, lse, select = operands
        scores = layer._index(leaves, x, pos, x.dtype)
        probs = A.head_sum_probs_reference(q, k, select) if lse is None \
            else A.head_sum_probs(q, k, lse, select, None,
                                  layer._block(x.shape[1]))
        return _plain_index_loss(scores, select, probs)
    return learned


@pytest.mark.parametrize("remat", (0, 1))
@pytest.mark.parametrize("impl", ("ref", "flash"))
def test_the_indexer_gradients_made_in_the_forward_are_plain_autodiffs(
        monkeypatch, impl, remat):
    """The indexer's leaves' gradients, made in the forward pass and
    scaled by the cotangent in the backward, are those of plain autodiff
    through the loss, under ``remat`` (the model's policy) and without,
    through XLA's scores and the kernels', at an ``index_loss_coef`` and
    an objective's scale that make the cotangent neither 1 nor the
    coefficient; the layer's input and every other leaf get bit for bit
    the gradients they got."""
    from cxxnet_tpu.layers import seq
    from cxxnet_tpu.model import _REMAT_POLICY
    S = 16
    x, w = data(S, 11)
    layer = dsa_layer(attn_impl=impl, index_loss_coef=0.3)
    params = weights(layer, S, 11)

    def gradients():
        # traced anew each time: jax.checkpoint keeps a function's trace
        def apply(p, x_):
            y, new = run(layer, p, x_)
            return y, new["_aux_loss"], new["dsa_stats"]
        if remat:
            apply = jax.checkpoint(apply, policy=_REMAT_POLICY)

        def objective(p, x_):
            y, aux, stats = apply(p, x_)
            return -1.7 * (jnp.sum(y * w) + aux), stats
        return jax.jit(jax.grad(objective, (0, 1), has_aux=True))(params, x)
    got = gradients()
    monkeypatch.setattr(seq, "_index_learned", _plain_index_learned(layer))
    want = gradients()
    close(got[1], want[1], 1e-6)            # L_I and the set's counts
    assert float(got[1][1]) > 0
    (g_params, g_x), (w_params, w_x) = got[0], want[0]
    assert np.array_equal(np.asarray(g_x), np.asarray(w_x))
    for name in params:
        for a, b in zip(jax.tree_util.tree_leaves(g_params[name]),
                        jax.tree_util.tree_leaves(w_params[name])):
            if name in seq._INDEX_LEAVES:
                assert np.abs(np.asarray(b)).max() > 0, name
                close(a, b, 1e-5)
            else:
                assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_the_kind_dsa_needs_its_indexer_and_gqa_takes_the_same_keys():
    with pytest.raises(ValueError, match="index_topk"):
        dsa_layer(topk=0)
    with pytest.raises(ValueError, match="no window"):
        dsa_layer(kind="gqa", window=4)
    as_gqa = dsa_layer(kind="gqa")
    params = weights(as_gqa, 24, 2)
    x, _ = data(24, 2)
    close(run(as_gqa, params, x)[0], run(dsa_layer(), params, x)[0], 0)
    from cxxnet_tpu.config import ConfigError, parse_config_string
    from cxxnet_tpu.graph import build_graph
    with pytest.raises(ConfigError, match="unknown layer type"):
        build_graph(parse_config_string(
            "netconfig=start\nlayer[0->1] = dsa2:a\nnetconfig=end\n"
            "input_shape = 1,1,8\n"))


def test_gqa_without_the_new_keys_traces_what_it_traced():
    """With no indexer, no q/k norm and no position rows the layer's
    jaxpr is the one it had: the projections, the rotary by the token's
    index and the causal attention, nothing of the new ops."""
    from cxxnet_tpu.ops.attention import attention_reference, rope_partial

    def layer_of(**more):
        return create_layer(LayerSpec("gqa", "attn", [0], [1], [
            (k, str(v)) for k, v in dict(
                nhead=4, nkvhead=2, head_dim=D, rotary_dim=4,
                attn_impl="ref", **more).items()]), [])
    layer = layer_of()
    params = layer.init_params(jax.random.PRNGKey(0), [(E, 24, 1)])
    assert set(params) == {"q", "k", "v", "o"}
    assert layer.init_state([(E, 24, 1)]) == {}
    x, _ = data(24, 1)

    def before(p, x_):
        q, k, v = (jnp.einsum("bse,ehd->bshd", x_, p[nm]["wmat"])
                   for nm in ("q", "k", "v"))
        q, k = (rope_partial(a, layer.rope_freqs, layer.rope_mscale)
                for a in (q, k))
        o = attention_reference(q, k, v, causal=True, window=None)
        return jnp.einsum("bshd,hde->bse", o, p["o"]["wmat"])
    text = lambda f: str(jax.make_jaxpr(f)(params, x))
    now = text(lambda p, x_: run(layer, p, x_)[0])
    strip = lambda s: "".join(s.split())
    assert strip(now).count("dot_general") \
        == strip(text(before)).count("dot_general") == 6
    for op in ("sort", "while", "cumsum", "cummax", "rsqrt", "top_k"):
        assert op not in now, op
    close(run(layer, params, x)[0], before(params, x), 1e-6)


# -- the expert layer: this model's is the one the tree had ---------------------------------


def moe_layer(first, held):
    return create_layer(LayerSpec("moe", "moe", [0], [1], [
        ("router", "softmax_nodrop"), ("num_expert", "16"), ("topk", "3"),
        ("nhidden", "12"), ("shared_expert", "0"),
        ("routed_scaling_factor", "1"), ("expert_first", str(first)),
        ("expert_held", str(held)), ("init_sigma", "0.3"),
        ("random_type", "gaussian")]), [])


def test_the_sixteen_expert_shares_add_up_to_the_uncut_layer(ref):
    """16 experts over 16 chips of one each — the deployment's way at the
    toy's count: every chip routes over all 16 and computes its own
    expert's pairs; there is no shared expert, so the partial sums add up
    to the uncut layer as they stand."""
    whole = moe_layer(0, 16)
    params = whole.init_params(jax.random.PRNGKey(7), [(E, 24, 1)])
    assert "shared" not in params
    state = whole.init_state([(E, 24, 1)])
    x = jnp.asarray(np.random.RandomState(7).randn(2, 24, E), jnp.float32)
    ctx = ApplyCtx(train=True, compute_dtype=jnp.float32)
    c = dict(CONFIG, num_experts=16, expert_first=0,
             num_experts_published=16)
    total, held_pairs = 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for chip in range(16):
            share = dict(params, **{k: {"wmat": params[k]["wmat"][
                chip:chip + 1]} for k in "gho"})
            (y,), new = moe_layer(chip, 1).apply(
                share, state, [x[:, :, None, :]], ctx)
            got = y[:, :, 0, :]
            close(got, ref.experts(share, x, dict(
                c, num_experts=1, expert_first=chip)))
            total = total + got
            held_pairs += float(new["stats"][0])
        assert held_pairs == 2 * 24 * 3     # every pair on exactly one chip
        close(total, ref.experts(params, x, c))


# -- the whole toy model ---------------------------------------------------------------------


def _toy():
    with open(os.path.join(TOY, "configs", "keye_toy.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(TOY, "configs", "keye_toy.conf")) as f:
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
    want = ref.train_steps(ref.Model(cfg), params0, toks.astype(np.int32),
                           label.astype(np.int32), cfg["train"]["eta"])
    assert got[2] < got[0]
    np.testing.assert_allclose(got, [ce + index for ce, index in want],
                               atol=5e-5)
    assert all(index > 0 for _, index in want)
    kinds = [layer.spec.type for layer in tr.net.layers]
    assert kinds.count("dsa") == 2 and kinds.count("moe") == 2
    # the layers' counters came with the train metric
    tr.train_metric_report()
    from cxxnet_tpu.telemetry.registry import REGISTRY
    pairs = {labels[0]: child.value for labels, child in REGISTRY.get(
        "cxxnet_dsa_selected_pairs").samples()}
    assert pairs["b0_attn"] == pairs["b1_attn"] \
        == rows * ref.selected_pairs(8, S)
    loss = {labels[0]: child.value for labels, child in REGISTRY.get(
        "cxxnet_dsa_index_loss").samples()}
    assert loss["b0_attn"] > 0 and loss["b1_attn"] > 0
    tiles = {labels[0]: child.value for labels, child in REGISTRY.get(
        "cxxnet_attn_tiles_executed").samples()}
    assert tiles["b0_attn"] == tiles["b1_attn"] == 1    # 32 positions


def kernel_calls(text):
    """How often a jaxpr's text calls the score kernel, its backward and
    the head sum (the custom_vjp that wraps the score kernel under its
    name is not a call)."""
    bwd = text.count("name=index_scores_bwd")
    return {"index_scores": text.count("name=index_scores") - bwd
            - len(re.findall(r"custom_vjp_call\[\s*name=index_scores",
                             text)),
            "index_scores_bwd": bwd,
            "head_sum_probs": text.count("name=head_sum_probs")}


def _toy_net(remat, impl):
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.graph import build_graph
    from cxxnet_tpu.model import Network
    _, text = _toy()
    assert "remat = 1\n" in text and text.count("= dsa:") == 2
    text = text.replace("remat = 1\n", f"remat = {remat}\n").replace(
        "  qk_norm = 1\n", f"  qk_norm = 1\n  attn_impl = {impl}\n")
    cfg = parse_config_string(text + "batch_size = 2\n")
    return Network(build_graph(cfg), cfg)


@pytest.mark.parametrize("remat", (0, 1))
def test_the_indexer_runs_once_a_step(remat):
    """With ``remat`` (the model's policy) or without, the gradient's
    program calls the score kernel, its backward and the head sum once:
    the indexer's backward runs in the forward pass and its gradients are
    kept, so the forward that ``remat`` rebuilds has nothing of the
    indexer to make (it made both kernels again until the gradients were
    kept). The selection log names the path."""
    from cxxnet_tpu.model import _REMAT_POLICY
    from cxxnet_tpu.ops.fused import selection_counts, selection_site
    x, w = data(16, 5)
    layer = dsa_layer(attn_impl="flash")
    params = weights(layer, 16, 5)

    def apply(p, x_):
        y, new = run(layer, p, x_)
        return jnp.sum(y * w) + new["_aux_loss"]
    if remat:
        apply = jax.checkpoint(apply, policy=_REMAT_POLICY)
    log = {}
    with selection_site(log, "attn"):
        text = str(jax.make_jaxpr(jax.grad(apply))(params, x))
    assert kernel_calls(text) == {"index_scores": 1, "index_scores_bwd": 1,
                                  "head_sum_probs": 1}
    assert selection_counts(log)["index_grad"] == {"gqa.forward": 1}


def test_toy_net_under_remat_keeps_the_selection_and_the_kernels_output():
    """Loss and gradients under ``remat = 1`` are those under ``remat =
    0``; the rebuilt layers run neither the selection nor the attention's
    forward nor anything of the indexer a second time (two layers: two
    ``flash_fwd_select``, two ``flash_bwd_select``, two selection
    kernels, two score kernels with two backward kernels, two head
    sums), and the selection log names the sparse kernel, the selection
    kernel and where the indexer's gradients are made."""
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(0, 64, (2, 1, 1, 32)), jnp.float32)
    label = jnp.asarray(rng.randint(0, 64, (2, 32)), jnp.float32)
    out = {}
    for remat in (0, 1):
        net = _toy_net(remat, "flash")
        params, state = net.init(jax.random.PRNGKey(0))

        def loss(p):
            return net.apply(p, state, toks, label, None,
                             rng=jax.random.PRNGKey(1), train=True).loss
        out[remat] = jax.value_and_grad(loss)(params)
        if remat:
            text = str(jax.make_jaxpr(jax.grad(loss))(params))
            assert text.count("name=flash_fwd_select") == 2
            assert text.count("name=flash_bwd_select") == 2
            # the score kernel and the head sum run once, in the forward:
            # the indexer's gradients are kept (the public function of
            # the kernel's name wraps it, and the forward's name begins
            # the backward kernel's)
            assert kernel_calls(text) == {"index_scores": 2,
                                          "index_scores_bwd": 2,
                                          "head_sum_probs": 2}
            assert text.count("name=select_rows") == 2
            from cxxnet_tpu.ops.fused import selection_counts
            by = selection_counts(net.fused_log)
            assert by["attention"] == {"gqa.flash_sparse": 2}
            assert by["select"] == {"gqa.select_rows": 2}
            assert by["index_grad"] == {"gqa.forward": 2}
    close(out[1][0], out[0][0], 1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(out[1][1]),
                    jax.tree_util.tree_leaves(out[0][1])):
        close(a, b, 1e-5)
