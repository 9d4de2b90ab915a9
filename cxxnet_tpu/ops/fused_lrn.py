"""Fused cross-channel LRN: Pallas TPU kernels + jnp reference.

The classic cxxnet hand-fused CUDA kernel (lrn_layer-inl.hpp's chpool
expression) done TPU-natively: the jnp path materializes x^2, an
nsize-term shifted window sum, and the transcendental norm chain as
separate HBM-visible values (the optimization_barrier in
layers/conv.py even pins one on purpose), while this kernel holds one
(rows, C) tile in VMEM and does square, window-sum, powf, and the
final product in a single pass — one streaming read of x, one write
of y. The backward fuses the whole dx formula (including the
transposed-window term) into one kernel of its own, recomputing norm
from x in VMEM instead of saving it (HBM bytes are the scarce
resource of this step).

The channel window-sum is expressed as a matmul against a static
(C, C) band matrix — MXU-friendly, supported everywhere, and exact:
``win = x^2 @ B`` with ``B[i, c] = 1`` iff channel i falls in the
window centered at c. The backward needs the transposed window, so
``B^T`` rides along as a second constant input.

``fused_lrn`` returns y or ``None`` when the shape/dtype is
unsupported (caller falls back to the jnp reference).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

from .fused import (FusedSpmd, batch_divisible, island, note_fallback,
                    note_fused, out_struct, row_block, sublane_mult,
                    supported_dtype, use_interpret)


def lrn_reference(x: jax.Array, nsize: int, alpha: float, beta: float,
                  knorm: float) -> jax.Array:
    """Golden jnp implementation (layers/conv.py LRNLayer math, minus
    the fusion barrier — the kernel needs no fence)."""
    sq = jnp.square(x)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0),) * (x.ndim - 1)
                     + ((half, nsize - 1 - half),))
    c = x.shape[-1]
    win = sum(padded[..., i:i + c] for i in range(nsize))
    norm = knorm + (alpha / nsize) * win
    return x * jnp.exp(-beta * jnp.log(norm))


def band_matrix(c: int, nsize: int) -> np.ndarray:
    """(C, C) f32 window matrix: B[i, j] = 1 iff channel i is inside
    the centered window of output channel j."""
    half = nsize // 2
    i = np.arange(c)[:, None]
    j = np.arange(c)[None, :]
    return ((i >= j - half) & (i <= j + nsize - 1 - half)) \
        .astype(np.float32)


def _lrn_fwd_kernel(x_ref, band_ref, y_ref, *, ab, beta, knorm):
    xb = x_ref[...].astype(jnp.float32)
    win = jnp.dot(xb * xb, band_ref[...],
                  preferred_element_type=jnp.float32)
    norm = knorm + ab * win
    # norm**-beta as exp(-beta*log(norm)); norm >= knorm > 0
    y_ref[...] = (xb * jnp.exp(-beta * jnp.log(norm))).astype(y_ref.dtype)


def _lrn_bwd_kernel(x_ref, dy_ref, band_ref, bandt_ref, dx_ref, *,
                    ab, beta, knorm):
    """dx = dy * norm^-beta - 2*ab*beta * x * ((dy*x*norm^(-beta-1)) @ B^T)
    — norm recomputed in VMEM from x (one extra band matmul beats an
    HBM round trip for the saved norm)."""
    xb = x_ref[...].astype(jnp.float32)
    dyb = dy_ref[...].astype(jnp.float32)
    win = jnp.dot(xb * xb, band_ref[...],
                  preferred_element_type=jnp.float32)
    norm = knorm + ab * win
    p = jnp.exp(-beta * jnp.log(norm))            # norm^-beta
    t = dyb * xb * (p / norm)                     # dy*x*norm^(-beta-1)
    back = jnp.dot(t, bandt_ref[...], preferred_element_type=jnp.float32)
    dx_ref[...] = (dyb * p - 2.0 * ab * beta * xb * back) \
        .astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _lrn_2d(x2, band, bandt, ab, beta, knorm, interpret, bn):
    n, c = x2.shape
    nb = n // bn
    return pl.pallas_call(
        functools.partial(_lrn_fwd_kernel, ab=ab, beta=beta, knorm=knorm),
        grid=(nb,),
        in_specs=[pl.BlockSpec((bn, c), lambda j: (j, 0)),
                  pl.BlockSpec((c, c), lambda j: (0, 0))],
        out_specs=pl.BlockSpec((bn, c), lambda j: (j, 0)),
        out_shape=out_struct((n, c), x2.dtype, x2),
        interpret=interpret, name="lrn_fwd",
    )(x2, band)


def _lrn_fwd(x2, band, bandt, ab, beta, knorm, interpret, bn):
    return (_lrn_2d(x2, band, bandt, ab, beta, knorm, interpret, bn),
            (x2, band, bandt))


def _lrn_bwd(ab, beta, knorm, interpret, bn, res, dy):
    x2, band, bandt = res
    n, c = x2.shape
    nb = n // bn
    dx = pl.pallas_call(
        functools.partial(_lrn_bwd_kernel, ab=ab, beta=beta, knorm=knorm),
        grid=(nb,),
        in_specs=[pl.BlockSpec((bn, c), lambda j: (j, 0)),
                  pl.BlockSpec((bn, c), lambda j: (j, 0)),
                  pl.BlockSpec((c, c), lambda j: (0, 0)),
                  pl.BlockSpec((c, c), lambda j: (0, 0))],
        out_specs=pl.BlockSpec((bn, c), lambda j: (j, 0)),
        out_shape=out_struct((n, c), x2.dtype, x2, dy),
        interpret=interpret, name="lrn_bwd",
    )(x2, dy, band, bandt)
    # band/bandt are trace-time constants; zero cotangents (DCE'd)
    return dx, jnp.zeros_like(band), jnp.zeros_like(bandt)


_lrn_2d.defvjp(_lrn_fwd, _lrn_bwd)


def fused_lrn(x: jax.Array, nsize: int, alpha: float, beta: float,
              knorm: float, interpret: Optional[bool] = None,
              block_rows: int = 256,
              spmd: Optional[FusedSpmd] = None):
    """Fused LRN over the trailing channel axis of an NHWC node.
    Returns y (x.dtype) or ``None`` when unsupported. With ``spmd``
    the kernel runs as a shard_map island over the batch dim — LRN is
    row-local (the window runs over channels), so the island needs no
    collectives and its shard_map transpose is exact; the band
    matrices ride as closed-over constants."""
    if not supported_dtype(x) or x.ndim != 4 or knorm <= 0:
        note_fallback("lrn_unsupported")
        return None
    c = x.shape[-1]
    n = x.size // c
    if c > 1024:          # (C, C) band must stay comfortably in VMEM
        note_fallback("lrn_channels")
        return None
    if spmd is not None:
        if not batch_divisible(spmd, x.shape[0]):
            note_fallback("lrn_batch_indivisible")
            return None
        n_local = n // spmd.n_shards
    else:
        n_local = n
    target = max(8, min(block_rows, (1 << 20) // max(4 * c, 1) // 8 * 8))
    bn = row_block(n_local, target, mult=sublane_mult(x))
    if bn is None:
        note_fallback("lrn_shape")
        return None
    with note_fused("lrn"):
        band = jnp.asarray(band_matrix(c, nsize))
        itp = use_interpret(interpret)
        args = (band, band.T, float(alpha) / nsize, float(beta),
                float(knorm), itp, bn)
        if spmd is not None:
            return island(
                spmd, lambda xl: _lrn_2d(xl.reshape(-1, c),
                                         *args).reshape(xl.shape),
                in_batch=(True,), out_batch=True, interpret=itp)(x)
        y = _lrn_2d(x.reshape(n, c), *args)
        return y.reshape(x.shape)
