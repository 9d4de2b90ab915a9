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
    """The fused Pallas backward (dq + dk/dv kernels from the saved
    logsumexp) must match autodiff of the plain reference."""
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
