"""Convolution, pooling, and LRN layers, TPU-native.

The reference implements conv as im2col + grouped GEMM with memory-bounded
chunking (convolution_layer-inl.hpp:13-231) and ships a cuDNN specialization;
pooling as mshadow pool/unpool expressions (pooling_layer-inl.hpp) with
*ceil-mode* output shapes; LRN as a cross-channel chpool expression
(lrn_layer-inl.hpp). Here conv lowers to ``lax.conv_general_dilated`` in NHWC
(XLA tiles it onto the MXU directly — no im2col staging or temp_col_max
chunking needed), pooling to ``lax.reduce_window`` with explicit asymmetric
padding to reproduce ceil-mode shapes, and LRN to a pad+slice window sum that
XLA fuses.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .base import ApplyCtx, Layer, Shape3, is_flat, register_layer

@register_layer("conv")
class ConvolutionLayer(Layer):
    """2-D convolution with groups (convolution_layer-inl.hpp:13-231).

    Weight layout HWIO ``(kh, kw, cin/group, cout)``; output spatial size is
    floor((in + 2p - k)/stride) + 1 as in the reference (:174-178).
    """
    has_params = True
    # pipeline-parallel manual tensor parallelism: output-channel weight
    # slices per 'model' shard, activations all-gathered on the channel
    # axis after apply (see Network.tp_manual_plan)
    tp_manual_axis = -1

    def infer_shapes(self, in_shapes: List[Shape3]) -> List[Shape3]:
        self.check_n(in_shapes, 1, 1)
        c, y, x = in_shapes[0]
        hp = self.hp
        if hp.num_channel <= 0:
            raise ValueError(f"conv {self.name!r}: nchannel must be set")
        if hp.kernel_height <= 0 or hp.kernel_width <= 0:
            raise ValueError(f"conv {self.name!r}: kernel_size must be set")
        if c % hp.num_group or hp.num_channel % hp.num_group:
            raise ValueError(f"conv {self.name!r}: channels must divide ngroup")
        if hp.kernel_height > y + 2 * hp.pad_y or \
                hp.kernel_width > x + 2 * hp.pad_x:
            raise ValueError(
                f"conv {self.name!r}: kernel size exceeds padded input")
        oy = (y + 2 * hp.pad_y - hp.kernel_height) // hp.stride + 1
        ox = (x + 2 * hp.pad_x - hp.kernel_width) // hp.stride + 1
        self._cin = c
        return [(hp.num_channel, oy, ox)]

    def init_params(self, key, in_shapes):
        hp = self.hp
        kh, kw = hp.kernel_height, hp.kernel_width
        cin_g = self._cin // hp.num_group
        shape = (kh, kw, cin_g, hp.num_channel)
        fan_in = cin_g * kh * kw
        fan_out = (hp.num_channel // hp.num_group) * kh * kw
        params = {"wmat": hp.init_weight(key, shape, fan_in, fan_out)}
        if not hp.no_bias:
            params["bias"] = jnp.full((hp.num_channel,), hp.init_bias, hp.dtype)
        return params

    def apply(self, params, state, inputs, ctx):
        hp = self.hp
        if "wmat_scale" in params:
            # PTQ-derived int8 weights (quant/ptq.py): the int8 conv
            # bypasses the s2d fold (cin packing buys nothing once the
            # contraction is int8) but keeps the stem cin_pad — int8
            # zero-pad of the I dim is exact, same as the fp path
            from ..ops.quant import int8_conv
            x, w = inputs[0], params["wmat"]
            if (ctx.cin_pad and hp.num_group == 1
                    and x.shape[-1] < ctx.cin_pad):
                padc = ctx.cin_pad - x.shape[-1]
                x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, padc)))
                w = jnp.pad(w, ((0, 0), (0, 0), (0, padc), (0, 0)))
            y = int8_conv(
                x, w, params["wmat_scale"], params["act_scale"],
                params.get("bias"), ctx.fuse_act or "none",
                strides=(hp.stride, hp.stride),
                padding=((hp.pad_y, hp.pad_y), (hp.pad_x, hp.pad_x)),
                groups=hp.num_group)
            return [y], state
        x = inputs[0].astype(ctx.compute_dtype)
        w = params["wmat"].astype(ctx.compute_dtype)
        # stem channel padding (graph.stem_pad_plan via ctx.cin_pad):
        # zero-pad the input channels and the weight's I dim together —
        # exact (0 * 0 taps), params keep canonical shape, and the s2d
        # fold below then packs s*s*cin_pad channels
        if (ctx.cin_pad and hp.num_group == 1
                and x.shape[-1] < ctx.cin_pad):
            padc = ctx.cin_pad - x.shape[-1]
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, padc)))
            w = jnp.pad(w, ((0, 0), (0, 0), (0, padc), (0, 0)))
        # compute-dtype in, compute-dtype out: the MXU accumulates bf16
        # matmuls in f32 internally, and keeping activations in bf16
        # halves HBM traffic (mixed preferred_element_type would also break
        # the transpose/backward conv with mixed-dtype operands)
        if self._use_space_to_depth():
            y = self._apply_s2d(x, w)
        else:
            y = lax.conv_general_dilated(
                x, w,
                window_strides=(hp.stride, hp.stride),
                padding=((hp.pad_y, hp.pad_y), (hp.pad_x, hp.pad_x)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=hp.num_group)
        bias = params.get("bias")
        act = ctx.fuse_act or "none"   # graph-folded relu (act_fusion_plan)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        if act == "relu":
            y = jax.nn.relu(y)
        return [y], state

    def _use_space_to_depth(self) -> bool:
        """Stem convs (cin<=4, stride>=2 — e.g. AlexNet's 11x11/4 on RGB)
        run at ~13% of MXU peak lowered directly: 3 input channels leave
        most of the 128-wide systolic rows idle. Re-expressing the conv on a
        space-to-depth-blocked input (stride x stride patches folded into
        channels; the standard public TPU stem trick, e.g. MLPerf ResNet)
        packs s*s*cin channels instead and measures ~2x faster end-to-end
        on v5e. Exact — the kernel is zero-padded to a stride multiple, so
        extra taps contribute nothing."""
        hp = self.hp
        return (hp.num_group == 1 and hp.stride >= 2 and self._cin <= 4
                and (hp.kernel_height > 1 or hp.kernel_width > 1))

    def _apply_s2d(self, x, w):
        """conv(x, w, stride=s) == conv(space_to_depth(x, s), blocked w, 1).

        Geometry: with o = floor((H + 2p - k)/s) + 1 and k' = ceil(k/s),
        repad the input to exactly H' = s*(o - 1 + k') rows (top pad p,
        bottom pad/crop to fit — floor-mode tail rows are unused by the
        conv, so cropping them is exact), zero-pad the kernel to s*k' taps,
        then fold s x s blocks of both into channels: the resulting
        stride-1 conv over (H'/s, W'/s, s*s*cin) visits exactly the
        original windows. Weight stays in canonical HWIO (checkpoint/TP
        layout unchanged); the fold is traced, so grads flow back to it."""
        hp = self.hp
        s = hp.stride
        b, yy, xx, c = x.shape
        # output channels from the weight, not hp.num_channel: under the
        # pipeline path's manual tensor parallelism apply_stage hands us a
        # cout/tp slice of the filter
        cout = w.shape[-1]
        kh, kw = hp.kernel_height, hp.kernel_width
        kh2, kw2 = -(-kh // s) * s, -(-kw // s) * s    # ceil to stride
        oy = (yy + 2 * hp.pad_y - kh) // s + 1
        ox = (xx + 2 * hp.pad_x - kw) // s + 1
        hp_y, hp_x = s * (oy - 1) + kh2, s * (ox - 1) + kw2
        xp = jnp.pad(x, ((0, 0),
                         (hp.pad_y, max(0, hp_y - yy - hp.pad_y)),
                         (hp.pad_x, max(0, hp_x - xx - hp.pad_x)),
                         (0, 0)))[:, :hp_y, :hp_x, :]
        xs = xp.reshape(b, hp_y // s, s, hp_x // s, s, c)
        xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(
            b, hp_y // s, hp_x // s, s * s * c)
        wp = jnp.pad(w, ((0, kh2 - kh), (0, kw2 - kw), (0, 0), (0, 0)))
        ws = wp.reshape(kh2 // s, s, kw2 // s, s, c, cout)
        ws = ws.transpose(0, 2, 1, 3, 4, 5).reshape(
            kh2 // s, kw2 // s, s * s * c, cout)
        return lax.conv_general_dilated(
            xs, ws, window_strides=(1, 1), padding=((0, 0), (0, 0)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def param_pspecs(self):
        if self.hp.num_group > 1:
            return {}    # grouped conv: keep replicated (group dim conflicts)
        # output-channel (Megatron-style) sharding of the HWIO filter
        return {"wmat": (None, None, None, "model"), "bias": ("model",)}


def _pool_geometry(size: int, k: int, s: int, p: int):
    """Ceil-mode pooling geometry (pooling_layer-inl.hpp:111-120):
    out = min(size + 2p - k + s - 1, size + 2p - 1) // s + 1.
    Returns (out, extra) where extra is additional trailing pad needed so a
    VALID reduce_window over (p, p + extra) padding yields ``out``."""
    out = min(size + 2 * p - k + s - 1, size + 2 * p - 1) // s + 1
    needed = (out - 1) * s + k
    extra = max(0, needed - (size + 2 * p))
    return out, extra


class _PoolingLayer(Layer):
    """Max/avg/sum pooling (pooling_layer-inl.hpp:17-135). ``avg`` divides by
    k*k including padded cells, matching the reference's pool-then-scale."""
    reducer = "max"          # max | sum
    scale_avg = False
    pre_relu = False         # relu_max_pooling fusion (layer_impl-inl.hpp:58)
    tp_follow = True         # window over H,W only: channel-independent

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        c, y, x = in_shapes[0]
        hp = self.hp
        if hp.kernel_height <= 0 or hp.kernel_width <= 0:
            raise ValueError(f"{self.spec.type} {self.name!r}: must set kernel_size")
        if hp.kernel_height > y + 2 * hp.pad_y or \
                hp.kernel_width > x + 2 * hp.pad_x:
            raise ValueError(
                f"{self.spec.type} {self.name!r}: kernel exceeds padded input")
        oy, self._extra_y = _pool_geometry(y, hp.kernel_height, hp.stride, hp.pad_y)
        ox, self._extra_x = _pool_geometry(x, hp.kernel_width, hp.stride, hp.pad_x)
        return [(c, oy, ox)]

    def apply(self, params, state, inputs, ctx):
        hp = self.hp
        x = inputs[0]
        if self.pre_relu:
            x = jax.nn.relu(x)
        if self.reducer == "max":
            init, op = -jnp.inf, lax.max
        else:
            init, op = 0.0, lax.add
        pad = ((0, 0),
               (hp.pad_y, hp.pad_y + self._extra_y),
               (hp.pad_x, hp.pad_x + self._extra_x),
               (0, 0))
        # init must be a *numpy* scalar: a jnp constant becomes a tracer
        # under jit (jax>=0.9), defeating lax.reduce_window's monoid
        # detection and hitting the non-differentiable generic path
        y = lax.reduce_window(
            x, np.asarray(init, x.dtype), op,
            window_dimensions=(1, hp.kernel_height, hp.kernel_width, 1),
            window_strides=(1, hp.stride, hp.stride, 1),
            padding=pad)
        if self.scale_avg:
            y = y * (1.0 / (hp.kernel_height * hp.kernel_width))
        return [y], state


@register_layer("max_pooling")
class MaxPoolingLayer(_PoolingLayer):
    reducer = "max"


@register_layer("sum_pooling")
class SumPoolingLayer(_PoolingLayer):
    reducer = "sum"


@register_layer("avg_pooling")
class AvgPoolingLayer(_PoolingLayer):
    reducer = "sum"
    scale_avg = True


@register_layer("relu_max_pooling")
class ReluMaxPoolingLayer(_PoolingLayer):
    reducer = "max"
    pre_relu = True


@register_layer("insanity_max_pooling")
class InsanityPoolingLayer(_PoolingLayer):
    """Stochastic pooling (insanity_pooling_layer-inl.hpp:223-286): at train
    time pick a cell of each window with probability proportional to its
    (relu'd) activation; at eval fall back to max pooling over relu.
    """
    reducer = "max"
    pre_relu = True
    has_state = False

    def tp_followable(self, train):
        return not train     # train-time cell-pick rng (see Layer docstring)

    def apply(self, params, state, inputs, ctx):
        if not ctx.train:
            return super().apply(params, state, inputs, ctx)
        hp = self.hp
        x = jax.nn.relu(inputs[0])
        b, y, xw, c = x.shape
        kh, kw, s = hp.kernel_height, hp.kernel_width, hp.stride
        oy, ey = _pool_geometry(y, kh, s, hp.pad_y)
        ox, ex = _pool_geometry(xw, kw, s, hp.pad_x)
        xp = jnp.pad(x, ((0, 0), (hp.pad_y, hp.pad_y + ey),
                         (hp.pad_x, hp.pad_x + ex), (0, 0)))
        # gather all windows: (b, oy, ox, kh*kw, c)
        cells = jnp.stack(
            [xp[:, dy:dy + oy * s:s, dx:dx + ox * s:s, :]
             for dy in range(kh) for dx in range(kw)], axis=3)
        total = jnp.sum(cells, axis=3, keepdims=True)
        # uniform fallback when the window is all zeros
        probs = jnp.where(total > 0, cells / jnp.maximum(total, 1e-12),
                          1.0 / (kh * kw))
        u = jax.random.uniform(ctx.rng, (b, oy, ox, 1, c), x.dtype)
        cdf = jnp.cumsum(probs, axis=3)
        idx = jnp.sum((u > cdf).astype(jnp.int32), axis=3, keepdims=True)
        idx = jnp.clip(idx, 0, kh * kw - 1)
        out = jnp.take_along_axis(cells, idx, axis=3)[:, :, :, 0, :]
        return [out], state


@register_layer("lrn")
class LRNLayer(Layer):
    """AlexNet-style cross-channel local response normalization
    (lrn_layer-inl.hpp:12-90): out = in * (knorm + alpha/n * window_sum(in^2))^-beta
    with a centered channel window of ``local_size``.
    """

    def set_param(self, name, val):
        if name == "local_size":
            self.nsize = int(val)
        elif name == "alpha":
            self.alpha = float(val)
        elif name == "beta":
            self.beta = float(val)
        elif name == "knorm":
            self.knorm = float(val)

    def __init__(self, spec, global_cfg):
        self.nsize = 3
        self.alpha = 0.001
        self.beta = 0.75
        self.knorm = 1.0
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]
        sq = jnp.square(x)
        half = self.nsize // 2
        # window sum over channels via pad + strided slice sum; unrolled
        # python loop over the (small, static) window lets XLA fuse it all
        padded = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (half, self.nsize - 1 - half)))
        c = x.shape[-1]
        win = sum(padded[..., i:i + c] for i in range(self.nsize))
        norm = self.knorm + (self.alpha / self.nsize) * win
        # norm**-beta as exp(-beta*log(norm)) — same lowering class but
        # measurably faster than jnp.power's generic path on v5e, and
        # norm >= knorm > 0 so the log is safe
        out = x * jnp.exp(-self.beta * jnp.log(norm))
        # fusion fence: without it XLA fuses this whole transcendental
        # chain into a consumer conv's window computation (seen with
        # AlexNet's lrn->grouped-conv pairs), recomputing the LRN once per
        # kernel tap — measured 894 ms/step vs 15 ms with the barrier on a
        # v5e. The barrier only pins the one intermediate; everything else
        # still fuses (a perf-only hint: numerics are identical without).
        out = lax.optimization_barrier(out)
        return [out], state
