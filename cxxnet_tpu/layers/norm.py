"""Batch normalization (batch_norm / batch_norm_no_ma).

Reference: BatchNormLayer<xpu, moving_avg>
(/root/reference/src/layer/batch_norm_layer-inl.hpp:13-243). Semantics kept:
  * stats are per-channel for conv nodes, per-feature for flat nodes, computed
    over all remaining axes (biased variance, scale = channel/total);
  * gamma is visited under tag "wmat" and beta under "bias" (:29-32), so lr/wd
    scoping follows those tags;
  * ``batch_norm`` keeps running stats with ``bn_momentum`` (train-time EMA,
    used at eval); ``batch_norm_no_ma`` recomputes batch stats at eval;
  * running stats initialize to zero (:48-52) — reference parity.

Deliberate deviation — sync-BN: under the GSPMD train step the batch axis
is sharded over the 'data' mesh axis, so ``jnp.mean`` over axis 0 reduces
across ALL replicas (XLA inserts the cross-replica collective). The
reference computes per-GPU stats only because each GPU ran an independent
Backprop (batch_norm_layer-inl.hpp per-device stats, SURVEY §7 risks);
that was a hardware artifact, not a modeling choice, and global-batch
stats strictly dominate (per-GPU BN is the limit sync-BN approaches as
device count -> 1). Pinned by tests/test_layers.py::test_batch_norm_sync
on the 8-device mesh. No per-replica mode is offered: in a single GSPMD
program, shard-local statistics would require an extra shard_map seam for
a semantics nobody wants on TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Layer, is_flat, register_layer

class _BatchNormBase(Layer):
    moving_avg = True
    has_params = True
    # manual-tp follow: BN statistics are per-channel, so a channel-sharded
    # activation keeps flowing — gamma/beta and the running stats slice to
    # the local channels, and the stat-sink moments are all-gathered back
    # to full width after apply (Network.apply_stage)
    tp_follow = True
    tp_channel_params = ("wmat", "bias")
    tp_channel_state = ("running_exp", "running_var")
    # pipeline-parallel: BN is admissible in a pipeline body — train-time
    # normalization uses microbatch-local statistics (the same semantics as
    # the reference's per-GPU BN, batch_norm_layer-inl.hpp), while the raw
    # moments are recorded into ctx.stat_sink so the trainer can make ONE
    # exact full-batch running-stat update after the microbatch schedule
    pp_batch_stats = True

    def set_param(self, name, val):
        if name == "init_slope":
            self.init_slope = float(val)
        elif name == "eps":
            self.eps = float(val)
        elif name == "bn_momentum":
            self.bn_momentum = float(val)
        elif name == "bn_two_pass":
            # ADVICE r5: numerically-robust two-pass E[(x-mean)^2]
            # variance (an extra read of x) instead of the default
            # one-pass E[x^2]-E[x]^2
            self.two_pass = bool(int(val))

    def __init__(self, spec, global_cfg):
        self.init_slope = 1.0
        self.eps = 1e-10
        self.bn_momentum = 0.9
        self.two_pass = False
        super().__init__(spec, global_cfg)

    @property
    def has_state(self):
        return self.moving_avg

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        s = in_shapes[0]
        self._channel = s[2] if is_flat(s) else s[0]
        return [s]

    def init_params(self, key, in_shapes):
        return {
            "wmat": jnp.full((self._channel,), self.init_slope, self.hp.dtype),
            "bias": jnp.full((self._channel,), self.hp.init_bias, self.hp.dtype),
        }

    def init_state(self, in_shapes):
        if not self.moving_avg:
            return {}
        return {
            "running_exp": jnp.zeros((self._channel,), jnp.float32),
            "running_var": jnp.zeros((self._channel,), jnp.float32),
        }

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]
        axes = (0, 1, 2)   # NHWC: stats over batch+spatial, per channel;
        # flat nodes are (b,1,1,n) so this is per-feature over the batch
        slope, bias = params["wmat"], params["bias"]
        act = ctx.fuse_act or "none"   # graph-folded relu (act_fusion_plan)
        if ctx.train:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=axes)
            ex2 = jnp.mean(jnp.square(xf), axis=axes)
            if self.two_pass:
                # ADVICE r5 option: mean-dependent second read, no
                # cancellation risk (bn_two_pass = 1)
                raw_var = jnp.mean(jnp.square(xf - mean), axis=axes)
            else:
                # ONE-PASS moments: E[x^2]-E[x]^2 instead of the
                # two-pass E[(x-mean)^2]. The two-pass form makes the
                # variance reduction DEPEND on the mean, forcing XLA to
                # read the conv output twice; sibling independent
                # reductions fuse into one multi-output kernel (one
                # read). What the saved read is worth on the chip:
                # not measured (no cell sets bn_two_pass). Tradeoff:
                # f32 cancellation loses variance precision when
                # |mean| >> std (error ~1e-7 x mean^2 absolute);
                # acceptable for post-conv activations, and the clamp
                # guards the tiny-negative case, but a pathological
                # large-mean/low-var channel degrades toward
                # inv = rsqrt(eps). health = 1 watches for it: the
                # probe's bn_var_min reads 0 there and the
                # bn_collapse advice fires (telemetry/modelhealth.py).
                raw_var = ex2 - jnp.square(mean)
            var = jnp.maximum(raw_var, 0.0)
            inv = jax.lax.rsqrt(var + self.eps)
            out = (x - mean) * inv * slope + bias
            if act == "relu":
                out = jax.nn.relu(out)
            out = out.astype(x.dtype)
            if self.moving_avg:
                if ctx.stat_sink is not None:
                    # pipeline body: hand raw moments to the schedule (the
                    # trainer merges an exact full-batch EMA update after
                    # the ring); state is untouched here. Sink the TRUE
                    # second moment (not var+mean^2, which the clamp
                    # would have distorted)
                    ctx.stat_sink[self.name] = {"mean": mean, "sq": ex2}
                else:
                    m = self.bn_momentum
                    state = {
                        "running_exp": state["running_exp"] * m
                        + mean * (1 - m),
                        "running_var": state["running_var"] * m
                        + var * (1 - m),
                    }
            return [out], state
        if self.moving_avg:
            mean, var = state["running_exp"], state["running_var"]
        else:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=axes)
            if self.two_pass:
                var = jnp.mean(jnp.square(xf - mean), axis=axes)
            else:
                var = jnp.maximum(
                    jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean),
                    0.0)
        inv = jax.lax.rsqrt(var + self.eps)
        out = x * (slope * inv) + (bias - slope * mean * inv)
        if act == "relu":
            out = jax.nn.relu(out)
        return [out.astype(x.dtype)], state


@register_layer("batch_norm")
class BatchNormLayer(_BatchNormBase):
    moving_avg = True


@register_layer("batch_norm_no_ma")
class BatchNormNoMALayer(_BatchNormBase):
    moving_avg = False
