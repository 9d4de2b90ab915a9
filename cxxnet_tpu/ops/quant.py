"""int8 inference ops: static-scale activation quantization,
int8 x int8 -> int32 matmul/conv, dequant + bias + activation epilogue.

Serving-side counterpart of the PTQ pass (quant/ptq.py): weights arrive
pre-quantized in the params tree (``wmat`` int8 + ``wmat_scale``
per-out-channel f32 + ``act_scale`` scalar f32), activations are
quantized on the fly against the calibrated static ``act_scale``, the
contraction runs int8 x int8 with an int32 accumulator on XLA's own
int8 dot/conv (the MXU's native low-precision path), and the epilogue
applies dequantization, bias-add and the graph-folded relu. Inference-
only by design (the PR-5 pattern: quantized params never train).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


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


def int8_matmul(x: jax.Array, wq: jax.Array, w_scale: jax.Array,
                act_scale, bias: Optional[jax.Array] = None,
                act: str = "none") -> jax.Array:
    """Quantized linear: f32 ``x`` (m, k) against pre-quantized ``wq``
    (k, n) int8 with per-out-channel ``w_scale`` (n,). Activations are
    quantized against the static ``act_scale``; output is f32 after the
    dequant (+bias, +act) epilogue."""
    xq = quantize_act(x, act_scale)
    acc = lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return _epilogue(acc, dequant_factor(w_scale, act_scale), bias, act)


def int8_conv(x: jax.Array, wq: jax.Array, w_scale: jax.Array,
              act_scale, bias: Optional[jax.Array] = None,
              act: str = "none", *,
              strides: Tuple[int, int] = (1, 1),
              padding=((0, 0), (0, 0)),
              groups: int = 1) -> jax.Array:
    """Quantized convolution: f32 NHWC ``x`` against pre-quantized HWIO
    ``wq`` int8 with per-out-channel ``w_scale``. The contraction runs
    on XLA's int8 conv lowering (int32 accumulator), then the same
    epilogue as :func:`int8_matmul`."""
    xq = quantize_act(x, act_scale)
    acc = lax.conv_general_dilated(
        xq, wq,
        window_strides=strides,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=jnp.int32)
    return _epilogue(acc, dequant_factor(w_scale, act_scale), bias, act)
