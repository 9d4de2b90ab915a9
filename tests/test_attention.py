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
