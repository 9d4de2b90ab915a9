"""Fused uint8 stem decode-normalize: Pallas TPU kernel + jnp reference.

The ``device_normalize`` input path ships uint8
batches (4x smaller H2D) and normalizes on-device — but as a SEPARATE
jitted dispatch that reads the uint8 batch and writes a full fp32 copy
the train step then re-reads. Per pixel that is 1 (u8 read) + 4 (f32
write) + 4 (f32 step read) = 9 bytes before the stem conv sees anything.

This op is the in-step replacement (trainer ``input_fold``): the uint8
batch enters the train step directly and the cast/mean-subtract/scale
happens inside the compiled step, emitting the stem conv's input in the
compute dtype — 1 (u8 read) + compute-dtype write, with XLA free to fuse
the write into the space-to-depth producer chain (layers/conv.py). The
fp32 round-trip of the whole input batch is gone; at flagship shape
(256x224x224x3) that is ~310 MB of HBM traffic per step.

Two implementations, selected by the caller's ``fused`` flag:

* :func:`decode_normalize_reference` — plain jnp; inside jit XLA fuses
  it into the consumer. This is the default (and the escape hatch).
* :func:`fused_decode_normalize` — one Pallas streaming pass over the
  batch viewed as (rows, H*W*C) with the mean tiled/flattened to a
  single (1, H*W*C) row; returns None for unsupported shapes.

Numerics: the fold computes in f32 and casts ONCE to the compute dtype
— under an fp32 policy this is bit-identical to the eager
``_device_normalize`` path; under bf16/fp16 the input enters the model
already rounded to the compute dtype, which is exactly where the
layers' own ``astype(ctx.compute_dtype)`` puts it one op later.

No custom_vjp: the data path carries no gradient (the step
differentiates w.r.t. params only), so the kernel never sits on a
tangent path.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from .fused import (FusedSpmd, batch_divisible, island, note_fallback,
                    note_fused, out_struct, row_block, use_interpret)


def decode_normalize_reference(x: jax.Array, mean: Optional[jax.Array],
                               factor, out_dtype: Any) -> jax.Array:
    """Golden jnp implementation — Trainer._device_normalize's math
    (cast, subtract mean, scale) with the output in ``out_dtype``.
    ``mean`` broadcasts over the trailing axes: per-channel (C,) or a
    mean image (H, W, C). ``factor`` may be a traced scalar."""
    y = x.astype(jnp.float32)
    if mean is not None:
        y = y - mean
    y = y * factor
    return y.astype(out_dtype)


def _stem_kernel(*refs, has_mean):
    if has_mean:
        x_ref, mean_ref, f_ref, y_ref = refs
    else:
        x_ref, f_ref, y_ref = refs
        mean_ref = None
    # widened through int32: Mosaic has no direct uint8 -> float32 cast
    y = x_ref[...].astype(jnp.int32).astype(jnp.float32)
    if mean_ref is not None:
        y = y - mean_ref[...]
    y = y * f_ref[...]
    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "rb", "cb"))
def _stem_call(x2, mean_row, factor, out_dtype, interpret, rb, cb):
    n, cols = x2.shape
    has_mean = mean_row is not None
    kern = functools.partial(_stem_kernel, has_mean=has_mean)
    row_spec = pl.BlockSpec((rb, cb), lambda i, j: (i, j))
    vec_spec = pl.BlockSpec((1, cb), lambda i, j: (0, j))
    scal_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    ins = [x2] + ([mean_row] if has_mean else []) + \
        [factor.reshape(1, 1)]
    in_specs = [row_spec] + ([vec_spec] if has_mean else []) + [scal_spec]
    return pl.pallas_call(
        kern,
        grid=(n // rb, cols // cb),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=out_struct((n, cols), out_dtype, x2),
        interpret=interpret, name="stem_fwd",
    )(*ins)


def _col_block(cols: int, target: int = 2048, mult: int = 128
               ) -> Optional[int]:
    """Largest divisor of ``cols`` that is a multiple of ``mult`` (the
    lane tile) and <= target; None when there is none."""
    if cols <= 0 or cols % mult:
        return None
    best = None
    for b in range(mult, min(target, cols) + 1, mult):
        if cols % b == 0:
            best = b
    return best


def fused_decode_normalize(x: jax.Array, mean: Optional[jax.Array],
                           factor, out_dtype: Any,
                           interpret: Optional[bool] = None,
                           spmd: Optional[FusedSpmd] = None
                           ) -> Optional[jax.Array]:
    """One streaming Pallas pass: uint8 NHWC batch -> normalized
    compute-dtype batch. ``mean`` is None, per-channel (C,), or a mean
    image (H, W, C); ``factor`` a scalar (python or traced). Returns
    None when the shape is unsupported (caller uses the jnp
    reference). With ``spmd`` the pass runs as a shard_map island over
    the batch dim (pure data path — no collectives, no vjp)."""
    if x.dtype != jnp.uint8 or x.ndim != 4:
        note_fallback("stem_unsupported")
        return None
    b, h, w, c = x.shape
    cols = h * w * c
    b_local = b
    if spmd is not None:
        if not batch_divisible(spmd, b):
            note_fallback("stem_batch_indivisible")
            return None
        b_local = b // spmd.n_shards
    # batch rows: uint8 tiles pack (32, 128); accept the f32 sublane (8)
    # as a fallback so small CPU-test batches still exercise the kernel
    # in interpret mode
    rb = row_block(b_local, 128, mult=32) or row_block(b_local, 128,
                                                       mult=8)
    cb = _col_block(cols)
    if rb is None or cb is None:
        note_fallback("stem_shape")
        return None
    if mean is not None:
        mean = jnp.asarray(mean, jnp.float32)
        if mean.shape == (c,):
            # per-channel mean -> one flattened (1, H*W*C) row; the tile
            # is tiny (<=600 KB at flagship shape) and shared by every
            # batch row
            mean_row = jnp.tile(mean, h * w).reshape(1, cols)
        elif mean.shape == (h, w, c):
            mean_row = mean.reshape(1, cols)
        else:
            note_fallback("stem_mean_shape")
            return None
    else:
        mean_row = None
    with note_fused("stem"):
        factor = jnp.asarray(factor, jnp.float32)
        itp = use_interpret(interpret)
        if spmd is not None:
            # mean_row/factor may be traced step arguments — explicit
            # island inputs (replicated), never closure captures
            if mean_row is not None:
                def local(xl, mr, f):
                    y2l = _stem_call(xl.reshape(-1, cols), mr,
                                     f, jnp.dtype(out_dtype), itp, rb, cb)
                    return y2l.reshape(xl.shape)
                return island(spmd, local, in_batch=(True, False, False),
                              out_batch=True,
                              interpret=itp)(x, mean_row, factor)

            def local(xl, f):
                y2l = _stem_call(xl.reshape(-1, cols), None, f,
                                 jnp.dtype(out_dtype), itp, rb, cb)
                return y2l.reshape(xl.shape)
            return island(spmd, local, in_batch=(True, False),
                          out_batch=True, interpret=itp)(x, factor)
        y2 = _stem_call(x.reshape(b, cols), mean_row, factor,
                        jnp.dtype(out_dtype), itp, rb, cb)
        return y2.reshape(b, h, w, c)


def decode_normalize(x: jax.Array, mean: Optional[jax.Array], factor,
                     out_dtype: Any, fused: bool = False,
                     spmd: Optional[FusedSpmd] = None) -> jax.Array:
    """Dispatcher the trainer's folded step calls: the Pallas kernel
    when the fused suite is active (and the shape qualifies), else the
    jnp reference — both inside the compiled train step."""
    if fused:
        y = fused_decode_normalize(x, mean, factor, out_dtype, spmd=spmd)
        if y is not None:
            return y
    return decode_normalize_reference(x, mean, factor, out_dtype)
