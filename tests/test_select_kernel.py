"""The indexer's selection as one Pallas kernel (``select_rows``) under the
interpreter on the CPU, bit for bit against its oracle
``select_topk_reference`` (XLA's counting passes); a ``dsa`` layer's set
and ``dsa_stats`` under ``attn_impl = flash`` against ``ref``; and the
selection log, which keeps one entry per site and kind."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cxxnet_tpu.ops import attention as A

_NEG = -1e30


def _scores(kind, rows, positions, seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, positions, positions).astype(np.float32)
    if kind == "whole_numbers":          # ties by the dozen at every rank
        x = np.round(2 * x)
    elif kind == "signed_zeros":         # the relu's zeros, of both signs
        x = np.where(x > 0.5, x, 0.0).astype(np.float32)
        x[:, :, ::2] *= -1.0             # even columns: -0.0 and -x
    elif kind == "masked":               # the score kernel's fill above
        x = np.where(np.tril(np.ones((positions, positions), bool)), x,
                     _NEG).astype(np.float32)
    return jnp.asarray(x)


@pytest.mark.parametrize("kind, rows, positions, topk, block", [
    pytest.param("random", 1, 256, 64, 128, id="random"),
    pytest.param("random", 2, 256, 64, 64, id="two_rows_blocks_of_64"),
    # ties straddle the threshold: the position passes cannot be skipped
    pytest.param("whole_numbers", 2, 256, 64, 128, id="ties"),
    pytest.param("whole_numbers", 1, 384, 100, 128, id="ties_384"),
    pytest.param("signed_zeros", 1, 256, 40, 128, id="signed_zeros"),
    pytest.param("masked", 2, 256, 64, 128, id="masked_above"),
    # topk over some rows' causal length, and over every row's
    pytest.param("random", 1, 256, 200, 64, id="topk_past_most_rows"),
    pytest.param("random", 1, 256, 300, 128, id="topk_past_every_row"),
    # fewer positions than a block: the whole square in one step
    pytest.param("whole_numbers", 2, 48, 8, 48, id="one_block")])
def test_the_kernel_is_the_reference_bit_for_bit(kind, rows, positions, topk,
                                                  block):
    s = _scores(kind, rows, positions, positions + topk)
    want = np.asarray(A.select_topk_reference(s, topk)).astype(np.int8)
    got = np.asarray(A.select_rows(s, topk, block, True))
    assert got.dtype == np.int8 and got.shape == want.shape
    assert (got == want).all()
    assert got.sum() == rows * sum(min(t + 1, topk) for t in range(positions))


def test_signed_zeros_order_as_the_integer_image():
    """-0.0 sorts under +0.0 and a tie goes to the lower position: query
    5 of keys scoring [-0, +0, -0, +0, -0, +0] keeps the three +0.0."""
    row = jnp.asarray(np.array([[-0.0, 0.0] * 3] * 6, np.float32)[None])
    for sel in (A.select_rows(row, 3, 6, True),
                A.select_topk_reference(row, 3)):
        assert list(np.nonzero(np.asarray(sel)[0, 5])[0]) == [1, 3, 5]


def test_the_row_block_and_the_choice_of_kernel():
    assert A.select_rows_block(8192) == A.SELECT_ROWS
    assert A.select_rows_block(48) == 48
    assert A.select_rows_block(384) == 128
    assert A.select_rows_block(200) == 0           # no whole lane chunks
    s = _scores("random", 1, 256, 3)
    # the CPU backend takes XLA's passes unless the kernel is asked for
    for kernel in (None, False, True):
        got = A.select_topk(s, 64, kernel)
        assert got.dtype == jnp.int8
        assert (np.asarray(got)
                == np.asarray(A.select_topk_reference(s, 64))).all()


@pytest.mark.parametrize("positions, topk", [(256, 24), (48, 8)])
def test_a_dsa_layer_selects_alike_under_flash_and_ref(positions, topk):
    """The set ``select`` returns and the ``dsa_stats`` ``apply`` counts
    (pairs, tiles) are the same under the kernels (interpreted) as under
    XLA's code; the indexer's loss agrees to rounding."""
    from cxxnet_tpu.graph import LayerSpec
    from cxxnet_tpu.layers import ApplyCtx, create_layer
    from cxxnet_tpu.ops.fused import selection_counts, selection_site
    E = 16
    x = jnp.asarray(np.random.RandomState(positions).randn(
        2, positions, E), jnp.float32)
    sets, stats, logs = {}, {}, {}
    for impl in ("flash", "ref"):
        layer = create_layer(LayerSpec("dsa", "attn", [0], [1], [
            (k, str(v)) for k, v in dict(
                nhead=4, nkvhead=2, head_dim=8, index_heads=3,
                index_head_dim=4, index_topk=topk, attn_impl=impl,
                init_sigma=0.3, random_type="gaussian").items()]), [])
        params = layer.init_params(jax.random.PRNGKey(1), [(E, positions, 1)])
        state = layer.init_state([(E, positions, 1)])
        logs[impl] = {}
        with selection_site(logs[impl], "attn"):
            _, new = layer.apply(params, state, [x[:, :, None, :]],
                                 ApplyCtx(train=True,
                                          compute_dtype=jnp.float32))
        sets[impl] = np.asarray(layer.select(params, x))
        stats[impl] = np.asarray(new["dsa_stats"])
    assert sets["flash"].dtype == np.int8
    assert (sets["flash"] == sets["ref"]).all()
    assert stats["flash"][0] == stats["ref"][0] == 2 * sum(
        min(t + 1, topk) for t in range(positions))
    assert (stats["flash"][2:] == stats["ref"][2:]).all()
    assert abs(stats["flash"][1] - stats["ref"][1]) <= 1e-5
    assert dict(selection_counts(logs["flash"])["select"]) \
        == {"gqa.select_rows": 1}
    assert dict(selection_counts(logs["ref"])["select"]) \
        == {"gqa.select_ref": 1}


def test_the_selection_log_keeps_a_site_s_kinds_apart():
    """A ``dsa`` site reports its attention and its selection under one
    name, both kept; the other kinds' sites read as before; a retrace
    overwrites its own entries."""
    from cxxnet_tpu.ops.fused import (note_attention, note_grouped,
                                      note_select, selection_counts,
                                      selection_site, selection_summary)
    log = {}
    for _ in range(2):                   # a retrace counts once
        with selection_site(log, "b1_dsa"):
            note_attention("gqa.flash_sparse")
            note_select("gqa.select_rows")
        with selection_site(log, "b1_moe"):
            note_grouped("ragged_dot")
        with selection_site(log, "mla0"):
            note_attention("mla.flash")
        with selection_site(log, "full0"):
            note_attention("gqa.flash")
    note_select("outside any site")      # recorded nowhere
    assert log == {("b1_dsa", "attention"): "gqa.flash_sparse",
                   ("b1_dsa", "select"): "gqa.select_rows",
                   ("b1_moe", "grouped"): "ragged_dot",
                   ("mla0", "attention"): "mla.flash",
                   ("full0", "attention"): "gqa.flash"}
    # as the benchmark's line prints them: the kinds in their names' order
    assert list(selection_counts(log).items()) == [
        ("attention", {"gqa.flash_sparse": 1, "mla.flash": 1,
                       "gqa.flash": 1}),
        ("grouped", {"ragged_dot": 1}), ("select", {"gqa.select_rows": 1})]
    assert selection_summary(log) == (
        "selection: attention: gqa.flash=1, gqa.flash_sparse=1, "
        "mla.flash=1; grouped: ragged_dot=1; select: gqa.select_rows=1")
    # without a dsa site the counts are the kinds they always were
    del log[("b1_dsa", "attention")], log[("b1_dsa", "select")]
    assert set(selection_counts(log)) == {"attention", "grouped"}
