"""Fused pooling: Pallas TPU kernels + custom_vjp fused backward.

Two window regimes are fused (everything else falls back to the
layers' ``lax.reduce_window`` reference, doc/tasks.md "Fused kernels"):

* **tile** — non-overlapping square windows (``stride == kernel``, no
  padding, spatial dims divide): each input cell belongs to exactly one
  window, so the forward is a pure reshape-reduce and the backward is a
  single fused elementwise pass — no ``select-and-scatter`` (the
  notoriously expensive max-pool backward op on TPU). Covers the 2x2/2
  pools of the MNIST/bowl-class convnets.
* **global** — one window covering the whole spatial extent (the
  Inception-BN head's 7x7 global average pool): forward is a spatial
  mean/sum/max per (batch, channel), backward a broadcast.

Reducers: max / sum / avg (``scale_avg`` divides by kernel area
including padded cells — reference parity, here pad is 0 so it is just
1/k²). ``pre_relu`` folds relu_max_pooling's activation into the same
pass (max(relu(x)) on the forward; the backward masks out non-positive
cells, reproducing ``jax.nn.relu``'s zero-at-zero gradient exactly).

Max backward semantics match XLA's ``select-and-scatter`` reference:
the FIRST window cell (row-major over (dy, dx)) equal to the max gets
the whole cotangent — implemented as a statically unrolled first-match
sweep, capped at 16 cells (larger max windows fall back; avg/sum have
no per-cell scan and take any size).

Layout: x (B, H, W, C) is VIEWED as (B*oy, kh, ox, kw, C) — a pure
reshape since windows tile exactly — and blocked over the leading row
dim; the reduce runs over axes (1, 3) in VMEM.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.experimental import pallas as pl

from .fused import (FusedSpmd, batch_divisible, island, note_fallback,
                    note_fused, out_struct, row_block, sublane_mult,
                    supported_dtype, use_interpret)

#: max windows larger than this fall back (the first-match sweep is a
#: statically unrolled per-cell loop)
MAX_FIRST_MATCH_CELLS = 16


def pool_reference(x: jax.Array, kh: int, kw: int, stride: int,
                   reducer: str, scale_avg: bool,
                   pre_relu: bool) -> jax.Array:
    """Golden jnp implementation — layers/conv.py's ``_PoolingLayer``
    math for the pad-0/extra-0 geometries this module fuses."""
    if pre_relu:
        x = jax.nn.relu(x)
    if reducer == "max":
        init, op = -jnp.inf, lax.max
    else:
        init, op = 0.0, lax.add
    y = lax.reduce_window(
        x, np.asarray(init, x.dtype), op,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, stride, stride, 1),
        padding=((0, 0),) * 4)
    if scale_avg:
        y = y * (1.0 / (kh * kw))
    return y


# -- kernels ------------------------------------------------------------------

def _pool_fwd_kernel(x_ref, y_ref, *, reducer, pre_relu, scale):
    """x block (rb, kh, ox, kw, C) -> y block (rb, ox, C)."""
    x = x_ref[...]
    if pre_relu:
        x = jnp.maximum(x, 0)
    if reducer == "max":
        y = jnp.max(x, axis=(1, 3))
    else:
        y = jnp.sum(x, axis=(1, 3))
        if scale != 1.0:
            y = y * jnp.asarray(scale, y.dtype)
    y_ref[...] = y.astype(y_ref.dtype)


def _pool_bwd_max_kernel(x_ref, y_ref, dy_ref, dx_ref, *, kh, kw,
                         pre_relu):
    """First-match max backward: row-major (dy, dx) sweep; the first
    cell equal to the window max takes the whole cotangent (XLA
    select-and-scatter parity). ``pre_relu`` additionally masks cells
    that are not strictly positive (relu's zero-at-zero gradient)."""
    # compared in f32 whatever the storage dtype: the v5e has no bf16
    # vector compare (widening is exact, so the first match is the same)
    x = x_ref[...].astype(jnp.float32)
    xa = jnp.maximum(x, 0.0) if pre_relu else x
    ymax = y_ref[...].astype(jnp.float32)   # (rb, ox, C)
    dyv = dy_ref[...].astype(jnp.float32)
    taken = jnp.zeros(ymax.shape, jnp.bool_)
    for dy in range(kh):
        for dx in range(kw):
            cell = xa[:, dy, :, dx, :]
            hit = jnp.logical_and(cell == ymax,
                                  jnp.logical_not(taken))
            if pre_relu:
                hit = jnp.logical_and(hit, x[:, dy, :, dx, :] > 0)
            taken = jnp.logical_or(taken, hit)
            dx_ref[:, dy, :, dx, :] = jnp.where(
                hit, dyv, jnp.zeros_like(dyv)).astype(dx_ref.dtype)


def _pool_bwd_lin_kernel(dy_ref, dx_ref, *, kh, kw, scale):
    """sum/avg backward: every window cell gets scale * dy."""
    dyv = dy_ref[...]
    if scale != 1.0:
        dyv = dyv * jnp.asarray(scale, dyv.dtype)
    out = jnp.broadcast_to(dyv[:, None, :, None, :],
                           dx_ref.shape)
    dx_ref[...] = out.astype(dx_ref.dtype)


# -- pallas_call wrappers -----------------------------------------------------

def _fwd_call(xr, reducer, pre_relu, scale, interpret, rb):
    n, kh, ox, kw, c = xr.shape
    kern = functools.partial(_pool_fwd_kernel, reducer=reducer,
                             pre_relu=pre_relu, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(n // rb,),
        in_specs=[pl.BlockSpec((rb, kh, ox, kw, c),
                               lambda i: (i, 0, 0, 0, 0))],
        out_specs=pl.BlockSpec((rb, ox, c), lambda i: (i, 0, 0)),
        out_shape=out_struct((n, ox, c), xr.dtype, xr),
        interpret=interpret, name="pool_fwd",
    )(xr)


def _bwd_call(xr, y, dy, reducer, pre_relu, scale, interpret, rb):
    n, kh, ox, kw, c = xr.shape
    row5 = pl.BlockSpec((rb, kh, ox, kw, c), lambda i: (i, 0, 0, 0, 0))
    row3 = pl.BlockSpec((rb, ox, c), lambda i: (i, 0, 0))
    if reducer == "max":
        kern = functools.partial(_pool_bwd_max_kernel, kh=kh, kw=kw,
                                 pre_relu=pre_relu)
        return pl.pallas_call(
            kern, grid=(n // rb,),
            in_specs=[row5, row3, row3],
            out_specs=row5,
            out_shape=out_struct(xr.shape, xr.dtype, xr, dy),
            interpret=interpret, name="pool_bwd_max",
        )(xr, y, dy)
    kern = functools.partial(_pool_bwd_lin_kernel, kh=kh, kw=kw,
                             scale=scale)
    return pl.pallas_call(
        kern, grid=(n // rb,),
        in_specs=[row3],
        out_specs=row5,
        out_shape=out_struct(xr.shape, xr.dtype, dy),
        interpret=interpret, name="pool_bwd_lin",
    )(dy)


# -- custom_vjp ---------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _pool5(xr, reducer, pre_relu, scale, interpret, rb):
    return _fwd_call(xr, reducer, pre_relu, scale, interpret, rb)


def _pool5_fwd(xr, reducer, pre_relu, scale, interpret, rb):
    y = _fwd_call(xr, reducer, pre_relu, scale, interpret, rb)
    # max needs (x, max) residuals; sum/avg only x's SHAPE — the array
    # itself is never read by the linear backward kernel, so XLA DCEs
    # the residual's storage
    return y, (xr, y if reducer == "max" else None)


def _pool5_bwd(reducer, pre_relu, scale, interpret, rb, res, dy):
    xr, y = res
    dx = _bwd_call(xr, y, dy, reducer, pre_relu, scale, interpret, rb)
    return (dx,)


_pool5.defvjp(_pool5_fwd, _pool5_bwd)


def fused_pool(x: jax.Array, kh: int, kw: int, stride: int,
               pad: Tuple[int, int], extra: Tuple[int, int],
               reducer: str, scale_avg: bool, pre_relu: bool,
               interpret: Optional[bool] = None,
               block_rows: int = 64,
               spmd: Optional[FusedSpmd] = None) -> Optional[jax.Array]:
    """Fused pooling over an NHWC node, or ``None`` when the geometry
    is unsupported (caller runs its reduce_window reference):
    pad/extra must be 0 and windows must either tile exactly
    (stride == kh == kw, H % kh == 0, W % kw == 0) or be the single
    global window (kh == H and kw == W)."""
    if not supported_dtype(x) or x.ndim != 4 \
            or reducer not in ("max", "sum"):
        note_fallback("pool_unsupported")
        return None
    b, h, w, c = x.shape
    tiles = stride == kh == kw and h % kh == 0 and w % kw == 0
    if pad != (0, 0) or extra != (0, 0) \
            or not (tiles or (kh == h and kw == w)):
        # overlapping, padded or ceil-mode windows: reduce_window
        note_fallback("pool_geometry")
        return None
    if reducer == "max" and kh * kw > MAX_FIRST_MATCH_CELLS:
        note_fallback("pool_max_window")
        return None
    oy, ox = h // kh if kh != h else 1, w // kw if kw != w else 1
    scale = 1.0 / (kh * kw) if scale_avg else 1.0
    n = b * oy
    if spmd is not None:
        if not batch_divisible(spmd, b):
            note_fallback("pool_batch_indivisible")
            return None
        n_local = n // spmd.n_shards
    else:
        n_local = n
    # VMEM budget: ~1 MiB per (rb, kh, ox, kw, C) block as it is LAID
    # OUT — the two minor dims pad to the dtype's (sublane, 128) tile,
    # so a kw = 2 window costs a whole tile of sublanes (the max
    # backward holds two such blocks, double-buffered, plus their f32
    # working copies, inside the chip's 16 MiB of scoped VMEM). rb is
    # a leading block dim on both sides, so any divisor of the rows
    # will do: no sublane multiple is needed.
    sub = sublane_mult(x)
    per_row = (kh * ox * -(-kw // sub) * sub * -(-c // 128) * 128
               * x.dtype.itemsize)
    target = max(1, min(block_rows, (1 << 20) // per_row))
    rb = row_block(n_local, target, mult=1)
    if rb is None:
        note_fallback("pool_shape")
        return None
    with note_fused("pool"):
        itp = use_interpret(interpret)
        if spmd is not None:
            # pooling is row-local (windows never cross the batch dim):
            # collective-free island, exact shard_map transpose
            return island(
                spmd, lambda xl: _pool5(
                    xl.reshape(-1, kh, ox, kw, c), reducer, pre_relu,
                    float(scale), itp, rb
                ).reshape(xl.shape[0], oy, ox, c),
                in_batch=(True,), out_batch=True, interpret=itp)(x)
        xr = x.reshape(n, kh, ox, kw, c)
        y = _pool5(xr, reducer, pre_relu, float(scale), itp, rb)
        return y.reshape(b, oy, ox, c)
