"""int8 inference kernels: static-scale activation quantization,
int8 x int8 -> int32 matmul/conv, fused dequant + bias + activation
epilogue.

Serving-side counterpart of the PTQ pass (quant/ptq.py): weights arrive
pre-quantized in the params tree (``wmat`` int8 + ``wmat_scale``
per-out-channel f32 + ``act_scale`` scalar f32), activations are
quantized on the fly against the calibrated static ``act_scale``, the
contraction runs int8 x int8 with an int32 accumulator (the MXU's
native low-precision path), and the epilogue folds dequantization,
bias-add and the graph-folded relu into the same pass. Inference-only
by design — there is no custom_vjp here (the PR-5 pattern: quantized
params never train), so the Pallas kernel is a plain forward
``pallas_call``.

Shape eligibility for the fused matmul kernel follows the int8 MXU
tiling (min tile 32 x 128): rows a multiple of 32, K and N multiples of
128. Anything else — and every convolution — runs the jnp reference
path, which lowers to XLA's own int8 dot/conv (exact same integer
math, so outputs are bit-identical across the two paths' dequant).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl

from .fused import (FusedSpmd, batch_divisible, island, note_fallback,
                    note_fused, out_struct, row_block, use_interpret)


def quantize_act(x: jax.Array, act_scale) -> jax.Array:
    """Static-scale activation quantization: f32 -> int8 against the
    calibrated per-layer clip value. Symmetric: +-act_scale maps to
    +-127; values beyond the calibrated range saturate (that is the
    percentile-clip contract — rare outliers trade for resolution)."""
    s = jnp.asarray(act_scale, jnp.float32)
    q = jnp.round(jnp.clip(x.astype(jnp.float32) / s, -1.0, 1.0) * 127.0)
    return q.astype(jnp.int8)


def dequant_factor(w_scale: jax.Array, act_scale) -> jax.Array:
    """Per-out-channel f32 factor turning the int32 accumulator back
    into real units: acc * (act_scale/127) * w_scale."""
    return w_scale.astype(jnp.float32) * (
        jnp.asarray(act_scale, jnp.float32) / 127.0)


def _epilogue(acc_i32: jax.Array, factor: jax.Array,
              bias: Optional[jax.Array], act: str) -> jax.Array:
    y = acc_i32.astype(jnp.float32) * factor
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if act == "relu":
        y = jax.nn.relu(y)
    return y


# -- fused Pallas matmul ------------------------------------------------------

def _q_mm_kernel(*refs, act, has_bias):
    if has_bias:
        x_ref, w_ref, f_ref, b_ref, y_ref = refs
    else:
        x_ref, w_ref, f_ref, y_ref = refs
        b_ref = None
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * f_ref[...]
    if has_bias:
        y = y + b_ref[...]
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y


def _q_mm_pallas(xq, wq, factor, bias, act, bm, bn, interpret):
    m, k = xq.shape
    n = wq.shape[1]
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        pl.BlockSpec((k, bn), lambda i, j: (0, j)),
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),
    ]
    args = [xq, wq, factor.reshape(1, n)]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        args.append(bias.astype(jnp.float32).reshape(1, n))
    return pl.pallas_call(
        functools.partial(_q_mm_kernel, act=act, has_bias=has_bias),
        grid=(m // bm, n // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=out_struct((m, n), jnp.float32, xq),
        interpret=interpret, name="int8_matmul_fwd",
    )(*args)


def _mm_blocks(m: int, k: int, n: int) -> Optional[Tuple[int, int]]:
    """(bm, bn) for the fused int8 matmul, or None when the shape does
    not tile the int8 MXU layout (min tile 32 x 128)."""
    if k % 128 or n % 128:
        return None
    bm = row_block(m, 256, mult=32)
    bn = row_block(n, 512, mult=128)
    if bm is None or bn is None:
        return None
    return bm, bn


def int8_matmul(x: jax.Array, wq: jax.Array, w_scale: jax.Array,
                act_scale, bias: Optional[jax.Array] = None,
                act: str = "none", *, fused: bool = False,
                spmd: Optional[FusedSpmd] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """Quantized linear: f32 ``x`` (m, k) against pre-quantized ``wq``
    (k, n) int8 with per-out-channel ``w_scale`` (n,). Activations are
    quantized against the static ``act_scale``; output is f32 after the
    fused dequant (+bias, +act) epilogue. ``fused=True`` attempts the
    Pallas kernel (falling back to the bit-identical jnp reference on
    ineligible shapes); ``spmd`` islands the kernel over the batch axis
    with weights/scales replicated, matching the PR-9 plumbing."""
    xq = quantize_act(x, act_scale)
    factor = dequant_factor(w_scale, act_scale)
    if fused and act in ("none", "relu"):
        m = xq.shape[0]
        m_local = m
        if spmd is not None:
            if not batch_divisible(spmd, m):
                note_fallback("quant_batch_indivisible")
                spmd = None
            else:
                m_local = m // spmd.n_shards
        blocks = _mm_blocks(m_local, xq.shape[1], wq.shape[1])
        if blocks is not None:
            bm, bn = blocks
            note_fused("int8_matmul")
            itp = use_interpret(interpret)
            if spmd is not None:
                return island(
                    spmd,
                    lambda xl, wl, fl, bl: _q_mm_pallas(
                        xl, wl, fl, bl, act, bm, bn, itp),
                    in_batch=(True, False, False, False),
                    out_batch=True, interpret=itp)(xq, wq, factor, bias)
            return _q_mm_pallas(xq, wq, factor, bias, act, bm, bn, itp)
        note_fallback("quant_mm_shape")
    acc = lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return _epilogue(acc, factor, bias, act)


def int8_conv(x: jax.Array, wq: jax.Array, w_scale: jax.Array,
              act_scale, bias: Optional[jax.Array] = None,
              act: str = "none", *,
              strides: Tuple[int, int] = (1, 1),
              padding=((0, 0), (0, 0)),
              groups: int = 1) -> jax.Array:
    """Quantized convolution: f32 NHWC ``x`` against pre-quantized HWIO
    ``wq`` int8 with per-out-channel ``w_scale``. The contraction runs
    on XLA's int8 conv lowering (int32 accumulator); dequant + bias +
    act fuse into the epilogue. No Pallas variant — the direct conv
    already hits the MXU via XLA, and the epilogue is elementwise."""
    xq = quantize_act(x, act_scale)
    acc = lax.conv_general_dilated(
        xq, wq,
        window_strides=strides,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=jnp.int32)
    return _epilogue(acc, dequant_factor(w_scale, act_scale), bias, act)
