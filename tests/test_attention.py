"""Attention op golden tests (the pairtest discipline, SURVEY §4): chunked
online-softmax and the Pallas flash kernel (interpret mode on CPU) vs the
jnp reference, forward and backward; ring attention on the 8-device mesh vs
the single-device reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops import (attention_reference, chunked_attention,
                            flash_attention)
from cxxnet_tpu.parallel.ring import ring_attention_sharded
from jax.sharding import Mesh, PartitionSpec as P


def _qkv(b=2, s=128, h=2, d=32, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_matches_reference(causal):
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=causal)
    out = chunked_attention(q, k, v, causal=causal, block_k=32)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_ragged_blocks(causal):
    # seq length not divisible by block: the tail-padding mask must not
    # leak into a causal mask for real keys (regression)
    q, k, v = _qkv(s=100)
    ref = attention_reference(q, k, v, causal=causal)
    out = chunked_attention(q, k, v, causal=causal, block_k=32)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    """The fused Pallas backward (one kernel: dq, dk and dv from one
    rebuild of each score tile out of the saved logsumexp) must match
    autodiff of the plain reference."""
    q, k, v = _qkv(s=64)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=32,
                                       block_k=32, interpret=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_flash_vjp_matches_chunked_vjp():
    """Random-cotangent vjp equality against the chunked implementation,
    with rectangular blocks (16x32) so grid accumulation order differs
    from every other path."""
    q, k, v = _qkv(s=96)
    g = jnp.asarray(np.random.RandomState(9).randn(*q.shape), jnp.float32)

    _, vjp_c = jax.vjp(lambda a, b, c: chunked_attention(
        a, b, c, causal=True, block_k=32), q, k, v)
    _, vjp_f = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, causal=True, block_q=16, block_k=32, interpret=True),
        q, k, v)
    for a, b in zip(vjp_c(g), vjp_f(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def _inputs(sq, sk, h, d, dv, dtype=jnp.float32, seed=3):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)
    return (mk(2, sq, h, d), mk(2, sk, h, d), mk(2, sk, h, dv),
            mk(2, sq, h, dv))


@pytest.mark.parametrize(
    "sq,sk,d,dv,causal,block_q,block_k,dtype,atol", [
        # v narrower than q.k, as latent attention has it (192 / 128)
        (64, 64, 24, 16, True, 32, 32, jnp.float32, 5e-5),
        # more keys than queries, every key seen by every query
        (64, 96, 16, 16, False, 32, 32, jnp.float32, 5e-5),
        # rectangular tiles, both ways
        (96, 96, 16, 16, True, 16, 32, jnp.float32, 5e-5),
        (96, 96, 16, 16, True, 32, 16, jnp.float32, 5e-5),
        # several k-blocks: a q-block's dq is added to across them
        (128, 128, 16, 16, True, 32, 32, jnp.float32, 5e-5),
        (128, 128, 16, 16, False, 64, 32, jnp.float32, 5e-5),
        # bf16 operands on the MXU, float32 sums, against the float32
        # reference on the same (rounded) inputs
        (128, 128, 24, 16, True, 32, 32, jnp.bfloat16, 2e-2),
    ], ids=["v_narrower", "sq_ne_sk", "tall_tiles", "wide_tiles",
            "four_k_blocks", "noncausal_k_blocks", "bf16"])
def test_flash_backward_cases(sq, sk, d, dv, causal, block_q, block_k,
                              dtype, atol):
    """Random-cotangent vjp of the one fused backward kernel against
    autodiff of the plain reference in float32."""
    q, k, v, g = _inputs(sq, sk, 2, d, dv, dtype)
    f32 = lambda a: a.astype(jnp.float32)
    out_r, vjp_r = jax.vjp(lambda a, b, c: attention_reference(
        a, b, c, causal=causal), f32(q), f32(k), f32(v))
    out_f, vjp_f = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, block_q=block_q, block_k=block_k,
        interpret=True), q, k, v)
    assert out_f.dtype == dtype
    np.testing.assert_allclose(f32(out_f), out_r, atol=atol)
    for a, b in zip(vjp_f(g), vjp_r(f32(g))):
        assert a.dtype == dtype and a.shape == b.shape
        np.testing.assert_allclose(f32(a), b, atol=atol)


def _held_to_the_reference(sq, h, hkv, d, dv, window, block_q, block_k):
    """Output, dq, dk and dv of the kernels (interpreter) against
    autodiff of ``attention_reference``, float32, a random cotangent."""
    rng = np.random.RandomState(sq + d + (window or 0))
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v, g = (mk(1, sq, h, d), mk(1, sq, hkv, d), mk(1, sq, hkv, dv),
                  mk(1, sq, h, dv))
    out_r, vjp_r = jax.vjp(lambda a, b, c: attention_reference(
        a, b, c, causal=True, window=window), q, k, v)
    out_f, vjp_f = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, True, None, block_q, block_k, True, window), q, k, v)
    np.testing.assert_allclose(out_f, out_r, atol=5e-5)
    for a, b in zip(vjp_f(g), vjp_r(g)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("h,hkv,d,dv,window", [
    (2, 2, 16, 16, None),       # the diagonal's tiles alone have an edge
    (2, 2, 16, 16, 1024),       # a band of one block: every tile an edge tile
    (2, 2, 16, 16, 1200),       # the trailing edge falls at two offsets
    (2, 2, 16, 16, 2400),       # wider than two blocks: interior tiles too
    (2, 2, 16, 16, 100),        # narrower than a sub-tile
    (4, 2, 16, 16, None),       # two query heads a key/value head
    (6, 2, 16, 16, 1024),
    (2, 2, 24, 16, None),       # v narrower than q.k
    (2, 2, 24, 16, 1200),
], ids=["causal", "window_is_block", "window_1200", "window_2400",
        "window_100", "grouped", "grouped_window", "v_narrower",
        "v_narrower_window"])
def test_flash_edge_tiles_go_by_sub_tiles(h, hkv, d, dv, window):
    """3072 positions at square blocks of 1024, the smallest block that
    is taken apart, three blocks a row: the interior tiles run without a
    mask, and an edge tile is multiplied by its sub-tiles of 256 — some
    left out, some without a mask, some masked at the sub-tile's
    shape."""
    from cxxnet_tpu.ops.attention import _edge_tiles, flash_tile_classes
    cls = flash_tile_classes(3072, 1024, window)
    assert cls["subtile"] == 256 and cls["sub_skipped"] > 0
    assert cls["pairs_multiplied"] < (cls["interior"] + cls["edge"]) * 1024 ** 2
    assert 0 in _edge_tiles(1024, window)
    _held_to_the_reference(3072, h, hkv, d, dv, window, 1024, 1024)


@pytest.mark.parametrize("block_q,block_k,window", [
    (1024, 512, None), (512, 1024, None), (1024, 512, 1200),
    (512, 1024, 1200)])
def test_flash_unsquare_blocks_take_the_whole_tile_body(
        monkeypatch, block_q, block_k, window):
    """Blocks that are not square never consult the table of sub-tiles:
    every executed tile is masked whole, as before, and agrees."""
    from cxxnet_tpu.ops import attention

    def no_table(*a):
        raise AssertionError("an unsquare tile was classified")
    monkeypatch.setattr(attention, "_edge_tiles", no_table)
    _held_to_the_reference(2048, 2, 2, 16, 16, window, block_q, block_k)


@pytest.mark.parametrize("positions,block,window", [
    (3072, 1024, None), (3072, 1024, 1024), (3072, 1024, 1200),
    (3072, 1024, 2400), (3072, 1024, 100), (3072, 1024, 1),
    (3072, 1024, 3072), (3072, 1024, 5000), (4096, 2048, 700),
    (2048, 512, 512), (1024, 256, 300), (1024, 128, 100),
    (1024, 128, None), (96, 32, 20), (48, 16, 16), (1024, 1024, None),
    (1024, 1024, 200)],
    ids=lambda v: str(v))
def test_flash_tile_classes_against_the_mask_itself(positions, block, window):
    """The counting function against brute force: ``_keep`` over the
    whole square, cut into the kernels' tiles and sub-tiles. A sub-tile
    counted as skipped holds no kept pair, one counted as mask-free
    holds only kept pairs, and the multiplied pairs follow."""
    from cxxnet_tpu.ops.attention import (_edge_tiles, _keep, _subtile,
                                          flash_tile_classes, flash_tiles)
    pos = np.arange(positions)
    keep = np.asarray(_keep(pos[:, None], pos[None, :], window))
    sub, table = _subtile(block), _edge_tiles(block, window)
    assert block % sub == 0 and (block < 1024) == (sub == block)
    want = dict.fromkeys(("interior", "edge", "sub_plain", "sub_masked",
                          "sub_skipped"), 0)
    for i in range(0, positions, block):
        for j in range(0, positions, block):
            tile = keep[i:i + block, j:j + block]
            if not tile.any():
                continue                    # never executed
            if tile.all():
                want["interior"] += 1
                assert i - j not in table
                continue
            want["edge"] += 1
            listed = {(a, b): mask for a, parts in table[i - j]
                      for first, n, mask in parts
                      for b in range(first, first + n)}
            assert all(mask is None or n == 1 for _, parts in table[i - j]
                       for _, n, mask in parts)
            for a in range(block // sub):
                for b in range(block // sub):
                    part = tile[a * sub:(a + 1) * sub, b * sub:(b + 1) * sub]
                    kind = "sub_plain" if part.all() else \
                        "sub_masked" if part.any() else "sub_skipped"
                    want[kind] += 1
                    # the kernels' own table says the same of this sub-tile
                    assert ((a, b) in listed) == bool(part.any())
                    if part.any():
                        assert (listed[(a, b)] is None) == bool(part.all())
                        assert part.all() or \
                            listed[(a, b)] == i - j + (a - b) * sub
    got = flash_tile_classes(positions, block, window)
    assert {k: got[k] for k in want} == want
    assert got["subtile"] == sub
    assert want["interior"] + want["edge"] == \
        flash_tiles(positions, block, window)[0]
    assert got["pairs_attended"] == int(keep.sum())
    assert got["pairs_multiplied"] == want["interior"] * block ** 2 + (
        want["sub_plain"] + want["sub_masked"]) * sub ** 2
    assert got["pairs_multiplied"] >= got["pairs_attended"]


def test_flash_tile_classes_at_the_cells_shapes():
    """8192 positions: a causal head at blocks of 1024 and a band of 512
    at blocks of 512, as the benchmark's two sequence cells run them."""
    from cxxnet_tpu.ops.attention import flash_tile_classes, flash_tiles
    assert flash_tiles(8192, 1024) == (36, 64)
    assert flash_tiles(8192, 512, 512) == (31, 256)
    full = flash_tile_classes(8192, 1024)
    assert (full["interior"], full["edge"]) == (28, 8)
    assert full["pairs_attended"] == 8192 * 8193 // 2
    band = flash_tile_classes(8192, 512, 512)
    assert (band["interior"], band["edge"]) == (0, 31)
    assert band["pairs_attended"] == 512 * 513 // 2 + (8192 - 512) * 512
    # blocks of 1024 go by sub-tiles of 256: 10 of a diagonal tile's 16
    assert (full["subtile"], full["sub_plain"], full["sub_masked"],
            full["sub_skipped"]) == (256, 48, 32, 48)
    assert full["pairs_multiplied"] == (28 * 16 + 80) * 256 ** 2
    assert full["pairs_multiplied"] / full["pairs_attended"] == \
        pytest.approx(1.0311, abs=1e-4)          # 1.1249 with the tiles whole
    # blocks of 512 are not taken apart: every tile masked whole
    assert (band["subtile"], band["sub_plain"], band["sub_masked"],
            band["sub_skipped"]) == (512, 0, 31, 0)
    assert band["pairs_multiplied"] == 31 * 512 ** 2
    assert band["pairs_multiplied"] / band["pairs_attended"] == \
        pytest.approx(1.9999, abs=1e-4)


def test_flash_residuals_outlive_checkpoint():
    """Under ``jax.checkpoint`` with the model's policy the kernel's
    output and logsumexp are kept, so the gradient holds the forward
    kernel once (twice under a bare checkpoint, which rebuilds it only
    to get them back) and the backward kernel once — and is the
    unwrapped gradient to the bit."""
    from cxxnet_tpu.model import _REMAT_POLICY
    q, k, v, _ = _inputs(64, 64, 2, 24, 16)
    w = jnp.asarray(np.random.RandomState(5).randn(24, 24) * 0.2,
                    jnp.float32)

    def layer(w, q, k, v):
        # q, k made inside, as a layer's projections make them: they are
        # rebuilt in the backward, the kernel's forward is not
        return flash_attention(q @ w, k @ w.T, v, causal=True, block_q=32,
                               block_k=32, interpret=True)

    def grad_of(f):
        loss = lambda *a: jnp.sum(f(*a) ** 2)
        g = jax.grad(loss, argnums=(0, 1, 2, 3))
        return str(jax.make_jaxpr(g)(w, q, k, v)), g(w, q, k, v)

    text, g_plain = grad_of(layer)
    assert (text.count("name=flash_fwd"), text.count("name=flash_bwd")) \
        == (1, 1)
    text, _ = grad_of(jax.checkpoint(layer))
    assert text.count("name=flash_fwd") == 2
    text, g_kept = grad_of(jax.checkpoint(layer, policy=_REMAT_POLICY))
    assert (text.count("name=flash_fwd"), text.count("name=flash_bwd")) \
        == (1, 1)
    assert "flash_bwd_dq" not in text and "flash_bwd_dkv" not in text
    for a, b in zip(g_plain, g_kept):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flash_backward_refuses_a_row_of_dq_beyond_vmem():
    """A head's float32 dq row is resident in VMEM for the whole head:
    a sequence too long for that is refused with its sizes, as a length
    the blocks do not divide is."""
    from cxxnet_tpu.ops.attention import _flash_backward
    S = 2 ** 17
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    with pytest.raises(ValueError, match=r"dq row \(131072 x 128 lanes"):
        jax.eval_shape(
            lambda q, k, v, o, lse, g: _flash_backward(
                q, k, v, o, lse, g, True, None, 1024, 1024, False),
            sd(1, S, 1, 64), sd(1, S, 1, 64), sd(1, S, 1, 64),
            sd(1, S, 1, 64), jax.ShapeDtypeStruct((1, S), jnp.float32),
            sd(1, S, 1, 64))


def test_flash_rejects_nondivisible_seq():
    q, k, v = _qkv(s=100)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("seq",))
    q, k, v = _qkv(s=128)
    ref = attention_reference(q, k, v, causal=causal)
    out = ring_attention_sharded(mesh, q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_differentiable():
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("seq",))
    q, k, v = _qkv(s=64, h=1, d=16)

    def loss(q, k, v):
        return jnp.sum(
            ring_attention_sharded(mesh, q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gather_kv_attention_matches_reference(causal):
    """gather_kv_attention (the pp-compatible sequence-parallel path) must
    agree with the reference on both causal modes, gradients included."""
    from cxxnet_tpu.ops.attention import gather_kv_attention
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("seq",))
    q, k, v = _qkv(s=128)

    def sharded(q, k, v):
        f = jax.shard_map(
            lambda a, b, c: gather_kv_attention(a, b, c, "seq",
                                                causal=causal),
            mesh=mesh,
            in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"))
        return f(q, k, v)

    ref = attention_reference(q, k, v, causal=causal)
    out = sharded(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g = jax.grad(lambda q: jnp.sum(sharded(q, k, v) ** 2))(q)
    g_ref = jax.grad(lambda q: jnp.sum(
        attention_reference(q, k, v, causal=causal) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-5)
