"""Ring attention: sequence/context parallelism over a mesh axis.

The reference framework has no sequence dimension at all (SURVEY §5), but
long-context support is first-class here: sequences are sharded over a
``'seq'`` mesh axis and attention runs as a ring — each device keeps its
local query shard and passes its key/value shard around the ring with
``lax.ppermute`` (one ICI hop per step), accumulating the online-softmax
statistics (running max / normalizer) exactly as the chunked/flash kernels
do block-locally. Peak memory per device is O(S_local^2) per step instead
of O(S^2); communication fully overlaps compute on TPU because ppermute
lowers to async collective-permute.

Use ``ring_attention`` inside an existing ``shard_map`` (axis_name bound),
or ``ring_attention_sharded`` to run one call end-to-end on a mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import _online_block_update


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = False,
                   scale: Optional[float] = None) -> jax.Array:
    """Attention over sequence shards. Call under shard_map/pmap with
    ``axis_name`` bound; q,k,v are local shards (B, S_local, H, D) of a
    global (B, S, H, D) array sharded on the sequence axis."""
    B, S_loc, H, D = q.shape
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    sc = (D ** -0.5) if scale is None else scale
    q_pos = me * S_loc + jnp.arange(S_loc)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        acc, m, l, k_cur, v_cur = carry
        # the k/v shard currently held originated on device (me - s) mod n
        src = (me - s) % n
        k_pos = src * S_loc + jnp.arange(S_loc)
        acc, m, l = _online_block_update(
            acc, m, l, q, k_cur, v_cur, q_pos, k_pos, sc, causal)
        # rotate shards one hop around the ring (skipped result unused on
        # the final step but keeping it unconditional lets XLA overlap the
        # permute of step s with the matmuls of step s+1)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (acc, m, l, k_nxt, v_nxt), None

    # the scan carry must be device-varying over every mesh axis the
    # inputs vary over (not just the ring axis — an enclosing shard_map may
    # add e.g. a 'data' axis); deriving the init values from q makes them
    # inherit exactly the right varying axes
    acc0 = jnp.zeros_like(q, shape=(B, H, S_loc, D), dtype=jnp.float32)
    m0 = jnp.full_like(q, -1e30, shape=(B, H, S_loc), dtype=jnp.float32)
    l0 = jnp.zeros_like(q, shape=(B, H, S_loc), dtype=jnp.float32)
    (acc, m, l, _, _), _ = lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention_sharded(mesh: Mesh, q: jax.Array, k: jax.Array,
                           v: jax.Array, seq_axis: str = "seq",
                           causal: bool = False,
                           scale: Optional[float] = None) -> jax.Array:
    """One-call ring attention: shards (B,S,H,D) over ``seq_axis`` of
    ``mesh``, runs the ring, returns the global result."""
    spec = P(None, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
