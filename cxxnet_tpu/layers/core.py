"""Core (non-conv) layers: fullc, activations, flatten, dropout, structural
layers, parametric activations.

Reference analogs cited per class; all forward math is expressed in plain
jnp so XLA fuses elementwise chains into neighboring matmuls/convs, and
jax.grad derives every backward pass the reference hand-writes.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from .base import (ApplyCtx, Layer, Params, Shape3, State, flat_size, is_flat,
                   register_layer)


def _flat2d(x: jax.Array) -> jax.Array:
    """View a (b,1,1,n) or general NHWC node as (b, features)."""
    return x.reshape(x.shape[0], -1)


def _as_node(x2d: jax.Array) -> jax.Array:
    """Lift (b, n) back to the canonical flat node layout (b,1,1,n)."""
    return x2d.reshape(x2d.shape[0], 1, 1, x2d.shape[1])


@register_layer("fullc")
class FullConnectLayer(Layer):
    """Fully-connected layer (fullc_layer-inl.hpp:14-145).

    Weight stored (in, out) so the forward is ``x @ W`` — transposed from the
    reference's (out, in) + dot(in, W^T); (in, out) is the layout XLA prefers
    for a row-major activations matmul on the MXU.
    """
    has_params = True
    # pipeline-parallel manual tensor parallelism: column-parallel weight
    # slices per 'model' shard, outputs all-gathered on the feature axis
    tp_manual_axis = -1

    def infer_shapes(self, in_shapes: List[Shape3]) -> List[Shape3]:
        self.check_n(in_shapes, 1, 1)
        if self.hp.num_hidden <= 0:
            raise ValueError(f"fullc layer {self.name!r}: nhidden must be set")
        self._in_num = flat_size(in_shapes[0])
        return [(1, 1, self.hp.num_hidden)]

    def init_params(self, key, in_shapes):
        kw, _ = jax.random.split(key)
        nh = self.hp.num_hidden
        params: Params = {
            "wmat": self.hp.init_weight(kw, (self._in_num, nh),
                                        self._in_num, nh)}
        if not self.hp.no_bias:
            params["bias"] = jnp.full((nh,), self.hp.init_bias, self.hp.dtype)
        return params

    def apply(self, params, state, inputs, ctx):
        x = _flat2d(inputs[0])
        if "wmat_scale" in params:
            # PTQ-derived int8 weights (quant/ptq.py): static-scale
            # activation quantization + int8 x int8 -> int32 matmul +
            # dequant/bias/act epilogue (ops/quant.py)
            from ..ops.quant import int8_matmul
            y = int8_matmul(x, params["wmat"], params["wmat_scale"],
                            params["act_scale"], params.get("bias"),
                            ctx.fuse_act or "none")
            return [_as_node(y)], state
        w = params["wmat"].astype(ctx.compute_dtype)
        y = jnp.dot(x.astype(ctx.compute_dtype), w)
        bias = params.get("bias")
        act = ctx.fuse_act or "none"   # graph-folded relu (act_fusion_plan)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        if act == "relu":
            y = jax.nn.relu(y)
        return [_as_node(y)], state

    def param_pspecs(self):
        # column-parallel over the hidden dim: out features sharded on
        # 'model'; GSPMD all-gathers at the next consumer when needed
        return {"wmat": (None, "model"), "bias": ("model",)}


class _ActivationLayer(Layer):
    """Elementwise activation (activation_layer-inl.hpp:12-44)."""
    fn = staticmethod(lambda x: x)
    tp_follow = True     # elementwise: channel-sharded inputs pass through

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def apply(self, params, state, inputs, ctx):
        return [self.fn(inputs[0])], state


@register_layer("relu")
class ReluLayer(_ActivationLayer):
    fn = staticmethod(jax.nn.relu)


@register_layer("sigmoid")
class SigmoidLayer(_ActivationLayer):
    fn = staticmethod(jax.nn.sigmoid)


@register_layer("tanh")
class TanhLayer(_ActivationLayer):
    fn = staticmethod(jnp.tanh)


@register_layer("softplus")
class SoftplusLayer(_ActivationLayer):
    fn = staticmethod(jax.nn.softplus)


@register_layer("flatten")
class FlattenLayer(Layer):
    """Reshape to a flat node (flatten_layer-inl.hpp:11-42).

    Feature order is (y, x, c) — self-consistent within this framework; the
    reference's NCHW flatten orders (c, y, x).
    """

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [(1, 1, flat_size(in_shapes[0]))]

    def apply(self, params, state, inputs, ctx):
        return [_as_node(_flat2d(inputs[0]))], state


@register_layer("dropout")
class DropoutLayer(Layer):
    """Inverted dropout; ``threshold`` = drop probability
    (dropout_layer-inl.hpp:12-66). Self-loop layer in the reference; here it
    simply maps input to output (identity at eval)."""
    tp_follow = True

    def tp_followable(self, train):
        return not train     # train-time mask rng: see base docstring

    def set_param(self, name, val):
        if name == "threshold":
            self.threshold = float(val)

    def __init__(self, spec, global_cfg):
        self.threshold = 0.0
        super().__init__(spec, global_cfg)
        if not (0.0 <= self.threshold < 1.0):
            raise ValueError("dropout: invalid threshold")

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]
        if not ctx.train or self.threshold == 0.0:
            return [x], state
        pkeep = 1.0 - self.threshold
        mask = jax.random.bernoulli(ctx.rng, pkeep, x.shape)
        return [jnp.where(mask, x / pkeep, 0.0).astype(x.dtype)], state


@register_layer("split")
class SplitLayer(Layer):
    """1->N fan-out (split_layer-inl.hpp:12-45); grad-sum comes free from AD."""

    def infer_shapes(self, in_shapes):
        if len(in_shapes) != 1:
            raise ValueError("split: exactly one input")
        return [in_shapes[0]] * len(self.spec.nindex_out)

    def apply(self, params, state, inputs, ctx):
        return [inputs[0]] * len(self.spec.nindex_out), state


class _ConcatBase(Layer):
    """Concatenate along the channel/feature axis.

    Reference has two variants (concat_layer-inl.hpp:12-79): ``concat`` on
    NCHW dim 3 (features of flat nodes) and ``ch_concat`` on dim 1 (channels).
    In NHWC both are the last axis, so they share one implementation. (For
    non-flat ``concat`` inputs the reference concatenates image *width*; that
    combination is unused by every shipped config and is rejected here.)
    """
    channel_concat = True

    def infer_shapes(self, in_shapes):
        if len(in_shapes) < 2 or len(in_shapes) > 4:
            raise ValueError(f"{self.spec.type}: supports 2..4 inputs")
        base = in_shapes[0]
        if not self.channel_concat:
            for s in in_shapes:
                if not is_flat(s):
                    raise ValueError(
                        "concat of non-flat nodes is not supported; use "
                        "ch_concat for channel concatenation")
            return [(1, 1, sum(s[2] for s in in_shapes))]
        for s in in_shapes:
            if s[1:] != base[1:]:
                raise ValueError("ch_concat: spatial dims must match")
        return [(sum(s[0] for s in in_shapes), base[1], base[2])]

    def apply(self, params, state, inputs, ctx):
        return [jnp.concatenate(inputs, axis=-1)], state


@register_layer("concat")
class ConcatLayer(_ConcatBase):
    channel_concat = False


@register_layer("ch_concat")
class ChConcatLayer(_ConcatBase):
    channel_concat = True


@register_layer("bias")
class BiasLayer(Layer):
    """Additive per-feature bias for flat nodes (bias_layer-inl.hpp:14-86)."""
    has_params = True
    tp_follow = True
    tp_channel_params = ("bias",)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        if not is_flat(in_shapes[0]):
            raise ValueError("bias layer requires a flat input node")
        self._n = in_shapes[0][2]
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        return {"bias": jnp.full((self._n,), self.hp.init_bias, self.hp.dtype)}

    def apply(self, params, state, inputs, ctx):
        return [inputs[0] + params["bias"]], state


def _xelu(x: jax.Array, b) -> jax.Array:
    """op::xelu (op.h): a > 0 ? a : a / b."""
    return jnp.where(x > 0, x, x / b)


@register_layer("xelu")
class XeluLayer(Layer):
    """Leaky relu with divisor slope b, default 5 (xelu_layer-inl.hpp:15-55)."""
    tp_follow = True

    def set_param(self, name, val):
        if name == "b":
            self.b = float(val)

    def __init__(self, spec, global_cfg):
        self.b = 5.0
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def apply(self, params, state, inputs, ctx):
        return [_xelu(inputs[0], self.b)], state


@register_layer("insanity", "rrelu")
class InsanityLayer(Layer):
    """Randomized leaky relu (insanity_layer-inl.hpp:14-102).

    Train: per-element random divisor slope ~ U[lb, ub]; eval: deterministic
    slope ``(ub-lb)/(log ub - log lb)`` (the expectation of 1/s inverted).
    The reference's calm_start/calm_end annealing mutates lb/ub by a
    cumulative step counter (a quadratic-drift bug); here annealing is a
    clean linear interpolation of (lb, ub) toward their midpoint over
    [calm_start, calm_end] updates, tracked in layer state.

    Pipelines (``pp_state_tick``): microbatches read the step counter
    frozen at its start-of-step value — exactly the unsharded step's
    pre-increment semantics — and the trainer advances it ONCE per
    training step after the ring (``state_tick``), not once per
    microbatch.
    """
    has_state = True
    pp_state_tick = True

    def set_param(self, name, val):
        if name == "lb":
            self.lb = float(val)
        elif name == "ub":
            self.ub = float(val)
        elif name == "calm_start":
            self.calm_start = int(val)
        elif name == "calm_end":
            self.calm_end = int(val)

    def __init__(self, spec, global_cfg):
        self.lb, self.ub = 5.0, 10.0
        self.calm_start = self.calm_end = 0
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def init_state(self, in_shapes):
        return {"step": jnp.zeros((), jnp.int32)}

    def state_tick(self, state):
        """One training step's deterministic state advance — applied by
        the pipeline trainer once per step after the ring."""
        return {"step": state["step"] + 1}

    def _bounds(self, step):
        if self.calm_end <= self.calm_start:
            return self.lb, self.ub
        mid = 0.5 * (self.lb + self.ub)
        t = jnp.clip((step - self.calm_start) /
                     (self.calm_end - self.calm_start), 0.0, 1.0)
        return self.lb + t * (mid - self.lb), self.ub + t * (mid - self.ub)

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]
        lb, ub = self._bounds(state["step"])
        if ctx.train:
            slope = jax.random.uniform(ctx.rng, x.shape, x.dtype) * (ub - lb) + lb
            new_state = {"step": state["step"] + 1}
        else:
            # eval divisor 1/E[1/s] = (ub-lb)/(log ub - log lb) — guard
            # the fully-annealed lb == ub case (linear annealing reaches
            # it exactly; the reference's eval formula is 0/0 there too,
            # insanity_layer-inl.hpp:71) with the analytic limit lb
            lb_, ub_ = jnp.float32(lb), jnp.float32(ub)
            denom = jnp.log(ub_) - jnp.log(lb_)
            slope = jnp.where(denom < 1e-8, 0.5 * (lb_ + ub_),
                              (ub_ - lb_) / jnp.maximum(denom, 1e-8))
            slope = slope.astype(x.dtype)
            new_state = state
        return [_xelu(x, slope)], new_state


@register_layer("prelu")
class PReluLayer(Layer):
    """Learnable per-channel negative slope with optional train-time noise
    (prelu_layer-inl.hpp:48-173). The slope is visited under tag "bias" in
    the reference, so it follows bias lr/wd scoping here too.
    """
    has_params = True
    param_tags = {"bias": "bias"}   # slope stored under key "bias"
    tp_follow = True
    tp_channel_params = ("bias",)

    def tp_followable(self, train):
        # train-time slope noise draws rng over the local channel shard —
        # same-keyed draws per shard would decorrelate from unsharded
        return not (train and self.random_noise > 0)

    def set_param(self, name, val):
        if name == "init_slope":
            self.init_slope = float(val)
        elif name == "random_slope":
            self.init_random = int(val)
        elif name == "random":
            self.random_noise = float(val)

    def __init__(self, spec, global_cfg):
        self.init_slope = 0.25
        self.init_random = 0
        self.random_noise = 0.0
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        s = in_shapes[0]
        self._channel = s[2] if is_flat(s) else s[0]
        return [s]

    def init_params(self, key, in_shapes):
        if self.init_random:
            slope = jax.random.uniform(key, (self._channel,),
                                       self.hp.dtype) * self.init_slope
        else:
            slope = jnp.full((self._channel,), self.init_slope, self.hp.dtype)
        return {"bias": slope}

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]
        slope = params["bias"]          # broadcasts over trailing channel axis
        if ctx.train and self.random_noise > 0:
            noise = jax.random.uniform(ctx.rng, x.shape, x.dtype)
            mask = slope * (1.0 + noise * self.random_noise * 2.0
                            - self.random_noise)
        else:
            mask = jnp.broadcast_to(slope, x.shape)
        mask = jnp.clip(mask, 0.0, 1.0)
        return [jnp.where(x > 0, x, x * mask)], state


@register_layer("fixconn")
class FixConnectLayer(Layer):
    """Fixed (non-learned) connection matrix loaded from a text file
    (fixconn_layer-inl.hpp:14-96). File format: ``rows cols`` header then
    row-major float entries, whitespace separated.
    """

    def set_param(self, name, val):
        if name == "weight_file":
            self.weight_file = val

    def __init__(self, spec, global_cfg):
        self.weight_file = ""
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        if not self.weight_file:
            raise ValueError("fixconn: weight_file must be set")
        data = np.loadtxt(self.weight_file, dtype=np.float32)
        if data.ndim == 1:
            rows, cols = int(data[0]), int(data[1])
            data = data[2:].reshape(rows, cols)
        self._wmat = jnp.asarray(data)
        if flat_size(in_shapes[0]) != self._wmat.shape[0]:
            raise ValueError(
                f"fixconn: input size {flat_size(in_shapes[0])} does not "
                f"match weight rows {self._wmat.shape[0]}")
        return [(1, 1, int(self._wmat.shape[1]))]

    def apply(self, params, state, inputs, ctx):
        y = jnp.dot(_flat2d(inputs[0]), self._wmat)
        return [_as_node(y)], state


@register_layer("maxout")
class MaxoutLayer(Layer):
    """Maxout (Goodfellow et al. 2013): channels split into groups of
    ``num_piece`` and the output takes the elementwise max per group
    (cout = cin / num_piece).

    The reference DECLARES kMaxout (layer.h:344) but ships no
    implementation (layer_impl-inl.hpp's factory has no case for it);
    this is a real implementation going beyond that parity point. Works
    on conv (b,h,w,c) and flat nodes (max over the trailing feature
    axis); pairs with a preceding conv/fullc exactly like the paper's
    affine-then-max formulation."""

    def set_param(self, name, val):
        if name == "num_piece":
            self.num_piece = int(val)

    def __init__(self, spec, global_cfg):
        self.num_piece = 2
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        c, y, x = in_shapes[0]
        # the trailing array axis holds channels for conv nodes (NHWC)
        # and features for flat nodes ((b,1,1,f) — base.to_nhwc)
        feat = x if is_flat(in_shapes[0]) else c
        if self.num_piece < 1 or feat % self.num_piece:
            raise ValueError(
                f"maxout: channel/feature count {feat} not divisible by "
                f"num_piece {self.num_piece}")
        if is_flat(in_shapes[0]):
            return [(1, 1, x // self.num_piece)]
        return [(c // self.num_piece, y, x)]

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]
        k = self.num_piece
        grouped = x.reshape(x.shape[:-1] + (x.shape[-1] // k, k))
        return [jnp.max(grouped, axis=-1)], state
