"""Sequence / transformer layers: embed, layernorm, rmsnorm, mha, mla, gqa,
shortconv, ffn, seqfc, add, lmloss.

TPU-idiomatic extension beyond the reference (which has no sequence axis —
fixed image tensors, /root/reference/src/layer/layer.h:33-39; SURVEY §5
"long-context: N/A"): these layers make attention models expressible in the
same config dialect, with tensor-parallel PartitionSpecs over the mesh
'model' axis (heads for attention, hidden for the FFN) and attention
implementations from cxxnet_tpu.ops (reference / chunked online-softmax /
Pallas flash). Ring-attention sequence parallelism over a 'seq' axis lives
in cxxnet_tpu.parallel.ring and shares the same math.

Node convention for sequences: logical shape3 ``(E, S, 1)`` -> array
``(batch, S, 1, E)`` (tokens on the y axis, features on the channel axis,
consistent with the framework's NHWC image convention). Token-id inputs are
flat nodes ``(1, 1, S)``.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import (INDEX_GRAD_RESIDUAL, SELECT_RESIDUAL,
                             attention_reference, chunked_attention,
                             flash_attention, flash_attention_select,
                             flash_tile_classes, flash_tiles,
                             head_sum_probs, head_sum_probs_reference,
                             index_scores, index_scores_backward,
                             index_scores_reference, rope,
                             rope_frequencies, rope_interleaved,
                             rope_partial, rope_sections,
                             select_rows_block, select_tiles, select_topk)
from .base import Layer, Shape3, register_layer
from .loss import LossLayerBase


def _seq(x: jax.Array) -> jax.Array:
    """(b, S, 1, E) -> (b, S, E)."""
    return x.reshape(x.shape[0], x.shape[1], x.shape[3])


def _unseq(x: jax.Array) -> jax.Array:
    """(b, S, E) -> (b, S, 1, E)."""
    return x.reshape(x.shape[0], x.shape[1], 1, x.shape[2])


@register_layer("embed")
class EmbedLayer(Layer):
    """Token embedding: flat id node (1,1,S) -> sequence node (E,S,1).
    ``nhidden`` = embedding dim, ``vocab_size`` = table rows."""
    has_params = True

    def set_param(self, name, val):
        if name == "vocab_size":
            self.vocab_size = int(val)

    def __init__(self, spec, global_cfg):
        self.vocab_size = 0
        super().__init__(spec, global_cfg)
        if self.vocab_size <= 0:
            raise ValueError(f"embed layer {spec.name!r} needs vocab_size")

    def infer_shapes(self, in_shapes: List[Shape3]) -> List[Shape3]:
        self.check_n(in_shapes, 1, 1)
        c, y, S = in_shapes[0]
        if c != 1 or y != 1:
            raise ValueError("embed expects a flat (1,1,S) token-id node")
        return [(self.hp.num_hidden, S, 1)]

    def init_params(self, key, in_shapes):
        return {"wmat": self.hp.init_weight(
            key, (self.vocab_size, self.hp.num_hidden),
            self.vocab_size, self.hp.num_hidden)}

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]
        ids = x.reshape(x.shape[0], -1).astype(jnp.int32)
        out = jnp.take(params["wmat"].astype(ctx.compute_dtype), ids, axis=0)
        return [_unseq(out)], state


@register_layer("layernorm")
class LayerNormLayer(Layer):
    """LayerNorm over the feature axis of a sequence node. Params are keyed
    gamma/beta, which the optimizer scopes into the 'bias' hyper group (so
    weight decay does not pull the multiplicative gamma toward 0)."""
    has_params = True

    def set_param(self, name, val):
        if name == "eps":
            self.eps = float(val)

    def __init__(self, spec, global_cfg):
        self.eps = 1e-5
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        e = in_shapes[0][0]
        return {"gamma": jnp.ones((e,), jnp.float32),
                "beta": jnp.zeros((e,), jnp.float32)}

    def apply(self, params, state, inputs, ctx):
        x = inputs[0].astype(jnp.float32)
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + self.eps)
        y = y * params["gamma"] + params["beta"]
        return [y.astype(ctx.compute_dtype)], state


def rms_normalize(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, reduced
    in float32 whatever ``x`` is; float32 out."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)


@register_layer("rmsnorm")
class RMSNormLayer(Layer):
    """RMSNorm over the feature axis of a sequence node: no mean is
    taken off and there is no beta. ``gamma`` follows the bias hyper
    group like layernorm's; ``eps`` defaults to 1e-6."""
    has_params = True

    def set_param(self, name, val):
        if name == "eps":
            self.eps = float(val)

    def __init__(self, spec, global_cfg):
        self.eps = 1e-6
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        return {"gamma": jnp.ones((in_shapes[0][0],), jnp.float32)}

    def apply(self, params, state, inputs, ctx):
        y = rms_normalize(inputs[0], params["gamma"], self.eps)
        return [y.astype(ctx.compute_dtype)], state


@register_layer("posembed")
class PosEmbedLayer(Layer):
    """Learned absolute position embedding added to a sequence node
    (E,S,1) -> (E,S,1). Alternative to rotary (``rope = 1`` on mha)."""
    has_params = True

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        e, s, _ = in_shapes[0]
        return {"wmat": self.hp.init_sigma *
                jax.random.normal(key, (s, e), jnp.float32)}

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]
        pe = params["wmat"].astype(ctx.compute_dtype)
        s_local = x.shape[1]
        if ctx.seq_axis is not None and s_local != pe.shape[0]:
            # sequence parallelism: the table is replicated but this shard
            # holds tokens at a global offset — same offset arithmetic as
            # the mha rope path
            off = jax.lax.axis_index(ctx.seq_axis) * s_local
            pe = jax.lax.dynamic_slice_in_dim(pe, off, s_local, axis=0)
        return [x + pe.reshape(1, s_local, 1, pe.shape[1])], state


class _SeqLinearMixin:
    """Shared init for (in_dim -> out_dim) projections on sequence nodes."""

    def _linear_params(self, key, in_dim, out_dim, no_bias):
        p = {"wmat": self.hp.init_weight(key, (in_dim, out_dim),
                                         in_dim, out_dim)}
        if not no_bias:
            p["bias"] = jnp.full((out_dim,), self.hp.init_bias, jnp.float32)
        return p


@register_layer("mha")
class MultiHeadAttentionLayer(Layer, _SeqLinearMixin):
    """Multi-head self-attention on a sequence node (E,S,1) -> (E,S,1).

    Config: ``nhead``, ``causal`` (0/1), ``attn_impl`` in
    {auto, ref, chunked, flash}, ``attn_block`` (flash/chunked block size).
    Tensor parallelism: q/k/v projections shard over heads on the mesh
    'model' axis, the output projection contracts over sharded heads — the
    TPU-native generalization of the reference's fullc_gather hybrid
    (/root/reference/src/updater/async_updater-inl.hpp:68-94).
    """
    has_params = True

    def set_param(self, name, val):
        if name == "nhead":
            self.nhead = int(val)
        elif name == "causal":
            self.causal = bool(int(val))
        elif name == "attn_impl":
            if val not in ("auto", "ref", "chunked", "flash"):
                raise ValueError(f"unknown attn_impl {val!r}")
            self.attn_impl = val
        elif name == "attn_block":
            self.attn_block = int(val)
        elif name == "rope":
            self.rope = bool(int(val))
        elif name == "rope_theta":
            self.rope_theta = float(val)

    def __init__(self, spec, global_cfg):
        self.nhead = 8
        self.causal = False
        self.attn_impl = "auto"
        self.attn_block = 128
        self.rope = False
        self.rope_theta = 10000.0
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        e, s, _ = in_shapes[0]
        if e % self.nhead:
            raise ValueError(
                f"mha {self.name!r}: dim {e} not divisible by nhead {self.nhead}")
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        e = in_shapes[0][0]
        h, d = self.nhead, e // self.nhead
        ks = jax.random.split(key, 4)
        p = {}
        for i, nm in enumerate(("q", "k", "v")):
            sub = self._linear_params(ks[i], e, e, self.hp.no_bias)
            sub["wmat"] = sub["wmat"].reshape(e, h, d)
            if "bias" in sub:
                sub["bias"] = sub["bias"].reshape(h, d)
            p[nm] = sub
        out = self._linear_params(ks[3], e, e, self.hp.no_bias)
        out["wmat"] = out["wmat"].reshape(h, d, e)
        p["o"] = out
        return p

    def param_pspecs(self):
        qkv = {"wmat": (None, "model", None), "bias": ("model", None)}
        return {"q": qkv, "k": qkv, "v": qkv,
                "o": {"wmat": ("model", None, None), "bias": None}}

    def _attend(self, q, k, v, ctx):
        from ..ops.fused import note_attention
        block = self.attn_block
        if ctx.seq_axis is not None:
            if ctx.seq_gather_kv:
                # pipeline-parallel stage: one k/v all-gather (safe inside
                # the stage's switch branch) instead of the ring
                from ..ops.attention import gather_kv_attention
                note_attention("gather_kv")
                return gather_kv_attention(q, k, v, axis_name=ctx.seq_axis,
                                           causal=self.causal)
            # sequence-parallel step (shard_map): q/k/v are local sequence
            # shards; the ring carries k/v around the mesh axis
            from ..parallel.ring import ring_attention
            note_attention("ring")
            return ring_attention(q, k, v, axis_name=ctx.seq_axis,
                                  causal=self.causal)
        impl = self.attn_impl
        if impl == "auto":
            # flash on TPU when the sequence tiles evenly, plain reference
            # for short sequences, chunked otherwise
            S = q.shape[1]
            if jax.default_backend() == "tpu" and S % block == 0:
                impl = "flash"
            else:
                impl = "ref" if S <= 512 else "chunked"
        note_attention(impl)
        if impl == "ref":
            return attention_reference(q, k, v, causal=self.causal)
        if impl == "chunked":
            return chunked_attention(q, k, v, causal=self.causal,
                                     block_k=block)
        return flash_attention(q, k, v, causal=self.causal,
                               block_q=block, block_k=block)

    def apply(self, params, state, inputs, ctx):
        x = _seq(inputs[0]).astype(ctx.compute_dtype)

        def proj(nm):
            w = params[nm]["wmat"].astype(ctx.compute_dtype)
            out = jnp.einsum("bse,ehd->bshd", x, w)
            if "bias" in params[nm]:
                out = out + params[nm]["bias"].astype(ctx.compute_dtype)
            return out

        q, k, v = proj("q"), proj("k"), proj("v")
        if self.rope:
            off = 0
            if ctx.seq_axis is not None:   # global positions for local shard
                off = jax.lax.axis_index(ctx.seq_axis) * q.shape[1]
            q, k = rope(q, self.rope_theta, off), rope(k, self.rope_theta, off)
        o = self._attend(q, k, v, ctx)
        wo = params["o"]["wmat"].astype(ctx.compute_dtype)
        y = jnp.einsum("bshd,hde->bse", o, wo)
        if "bias" in params["o"]:
            y = y + params["o"]["bias"].astype(ctx.compute_dtype)
        return [_unseq(y)], state


def flash_block(positions: int, largest: int = 1024) -> int:
    """The square block the flash kernel takes at ``positions``: the
    largest of ``largest``, .., 256, 128 that divides them, the whole
    row where there are fewer than 128, else 0 (no kernel)."""
    if positions < 128:
        return positions
    b = largest
    while b >= 128:
        if positions % b == 0:
            return b
        b //= 2
    return 0


def publish_flash_tiles(layer: str, positions: int, block: int,
                        window=None) -> None:
    """What the causal flash kernel does for one head of ``layer`` at
    square blocks of ``block`` (0: no kernel, nothing published), as
    gauges set while the net is built, from shapes: the score tiles its
    forward executes and the square's (``flash_tiles``), how many of the
    executed an edge crosses (the only ones that build a mask, taken by
    sub-tiles of ``cxxnet_attn_subtile``), and the score pairs that
    reach the MXU over the pairs attended (``flash_tile_classes``)."""
    if not block:
        return
    from ..telemetry.registry import get_registry
    done, total = flash_tiles(positions, block, window)
    cls = flash_tile_classes(positions, block, window)
    for what, value, text in (
            ("tiles_executed", done, "score tiles a head of the flash "
             "kernel's forward executes, at the layer's blocks"),
            ("tiles_total", total, "score tiles of a head's whole square, "
             "at the layer's blocks"),
            ("tiles_masked", cls["edge"], "executed score tiles the causal "
             "diagonal or the band's trailing edge crosses: taken by "
             "sub-tiles, the only ones a mask is built in"),
            ("subtile", cls["subtile"], "side of the square sub-tiles an "
             "edge tile is taken by"),
            ("pairs_multiplied_over_attended",
             cls["pairs_multiplied"] / cls["pairs_attended"], "score pairs "
             "the flash kernel multiplies over the pairs the mask keeps")):
        get_registry().gauge("cxxnet_attn_" + what, text,
                             labels=("layer",)).labels(layer).set(
                                 float(value))


@register_layer("mla")
class LatentAttentionLayer(Layer):
    """Multi-head latent attention (the DeepSeek-V2/V3 family's), causal,
    in its training ("prefill") form: keys and values are materialised
    from the compressed latent; the absorbed decode form and the latent
    cache belong to serving and are not here. With x a position's vector:

      c_q = RMS(x W_qa);  per head [q_nope ; q_rope] = c_q W_qb
      [c_kv ; k_rope] = x W_kva;  c_kv <- RMS(c_kv)
      per head [k_nope ; v] = c_kv W_kvb
      q_rope, k_rope rotated on INTERLEAVED pairs (``rope_interleaved``),
      k_rope ONE vector shared by all heads
      scores = (q_nope.k_nope + q_rope.k_rope) / sqrt(d_nope + d_rope)
      y = concat_h(softmax(scores) v) W_o            no bias anywhere

    Config: ``nhead``, ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rope_theta``, ``eps``, ``attn_impl`` in {auto, ref, flash}. q.k is
    ``d_nope + d_rope`` wide and v ``v_head_dim``: ``ref`` runs on XLA's
    dots at those widths (the tests' oracle), ``flash`` is the Pallas
    kernel, which takes v's width apart from q's and k's, at blocks of
    the largest of 1024, 512, 256, 128 that divides the positions (one
    block where there are fewer than 128). ``auto`` is the kernel on a
    TPU where such a block exists — it won the layer's A/B on the chip,
    33 ms against 528 for XLA's dots at 8192 positions, and blocks of
    1024 won among blocks (PERF.md section 6, PR 28) — else ``ref``.
    The kernel's backward is one kernel that builds each score tile
    once for dq, dk and dv, at the same blocks (1024 x 1024 won its A/B
    too: PERF.md section 6, PR 29); under ``remat = 1`` the model keeps
    the kernel's output and logsumexp and rebuilds only ``mla.proj``
    (q, k, v from the layer's input) in the backward pass."""
    has_params = True

    _INT = ("nhead", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim")

    def set_param(self, name, val):
        if name in self._INT:
            setattr(self, name, int(val))
        elif name in ("rope_theta", "eps"):
            setattr(self, name, float(val))
        elif name == "attn_impl":
            if val not in ("auto", "ref", "flash"):
                raise ValueError(f"unknown mla attn_impl {val!r}")
            self.attn_impl = val

    def __init__(self, spec, global_cfg):
        self.nhead = 8
        self.q_lora_rank = self.kv_lora_rank = 0
        self.qk_nope_head_dim = self.qk_rope_head_dim = 0
        self.v_head_dim = 0
        self.rope_theta = 10000.0
        self.eps = 1e-6
        self.attn_impl = "auto"
        super().__init__(spec, global_cfg)
        for k in self._INT:
            if getattr(self, k) <= 0:
                raise ValueError(f"mla layer {spec.name!r} needs {k}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("mla: qk_rope_head_dim must be even")

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        S = in_shapes[0][1]
        publish_flash_tiles(self.name, S, flash_block(S))
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        e, h = in_shapes[0][0], self.nhead
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        ks = jax.random.split(key, 5)
        w = self.hp.init_weight
        return {
            "qa": {"wmat": w(ks[0], (e, rq), e, rq)},
            "qnorm": {"gamma": jnp.ones((rq,), jnp.float32)},
            "qb": {"wmat": w(ks[1], (rq, h, dn + dr), rq, h * (dn + dr))},
            "kva": {"wmat": w(ks[2], (e, rkv + dr), e, rkv + dr)},
            "kvnorm": {"gamma": jnp.ones((rkv,), jnp.float32)},
            "kvb": {"wmat": w(ks[3], (rkv, h, dn + dv), rkv,
                               h * (dn + dv))},
            "o": {"wmat": w(ks[4], (h, dv, e), h * dv, e)},
        }

    def param_pspecs(self):
        return {"qb": {"wmat": (None, "model", None)},
                "kvb": {"wmat": (None, "model", None)},
                "o": {"wmat": ("model", None, None)}}

    def _attend(self, q, k, v):
        from ..ops.fused import note_attention
        impl, S = self.attn_impl, q.shape[1]
        blk = flash_block(S)
        if impl == "auto":
            impl = "flash" if jax.default_backend() == "tpu" and blk \
                else "ref"
        note_attention("mla." + impl)
        if impl == "ref":
            return attention_reference(q, k, v, causal=True)
        return flash_attention(q, k, v, True, None, blk, blk)

    def apply(self, params, state, inputs, ctx):
        if ctx.seq_axis is not None:
            raise ValueError("mla has no sequence-parallel path")
        cd = ctx.compute_dtype
        x = _seq(inputs[0]).astype(cd)
        dn, dr = self.qk_nope_head_dim, self.qk_rope_head_dim
        rkv = self.kv_lora_rank
        w = lambda nm: params[nm]["wmat"].astype(cd)
        with jax.named_scope("mla.proj"):
            c_q = rms_normalize(jnp.einsum("bse,er->bsr", x, w("qa")),
                                params["qnorm"]["gamma"], self.eps)
            q = jnp.einsum("bsr,rhd->bshd", c_q.astype(cd), w("qb"))
            kv = jnp.einsum("bse,er->bsr", x, w("kva"))
            c_kv = rms_normalize(kv[..., :rkv], params["kvnorm"]["gamma"],
                                 self.eps)
            k_rope = kv[..., rkv:][:, :, None, :]         # (B, S, 1, dr)
            kvb = jnp.einsum("bsr,rhd->bshd", c_kv.astype(cd), w("kvb"))
            k_nope, v = kvb[..., :dn], kvb[..., dn:]
            q_rope = rope_interleaved(q[..., dn:], self.rope_theta)
            k_rope = rope_interleaved(k_rope, self.rope_theta)
            q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    k_rope, k_nope.shape[:3] + (dr,))], axis=-1)
        with jax.named_scope("mla.attend"):
            o = self._attend(q, k, v)
        with jax.named_scope("mla.proj"):
            y = jnp.einsum("bshd,hde->bse", o, w("o"))
        return [_unseq(y)], state


#: the order of a sparse ``gqa`` layer's ``dsa_stats`` vector: the pairs
#: its selection keeps (all rows), the indexer's loss ``L_I`` (unweighted),
#: and the score tiles a head's kernels executed and the square's, at the
#: layer's blocks, a row (0 where no kernel block divides the positions)
DSA_STATS = ("selected_pairs", "index_loss", "tiles_executed",
             "tiles_total")


@jax.custom_vjp
def _gradients_together(tree):
    """The identity, whose backward hands all of ``tree``'s cotangents on
    at once (``optimization_barrier``): none is used before the last is
    made. A sparse ``gqa`` layer passes its input and its indexer's
    leaves through it, so the leaves' gradients (the kept ones times the
    cotangent: :func:`_index_learned`) are handed on with the input's,
    before the backward goes on to the layer before. No positions x
    positions square waits on the tie: the indexer's backward runs in the
    forward pass. While the backward rebuilt the indexer's scores and
    distribution, the untied scheduler put every layer's indexer backward
    off to the step's end, each holding its two squares until then, and
    with the tie the chip's compiler also picked another layout for them
    (PERF.md section 6)."""
    return tree


_gradients_together.defvjp(
    lambda tree: (tree, None),
    lambda _, grads: (jax.lax.optimization_barrier(grads),))


#: a sparse ``gqa`` layer's indexer's leaves: all that its loss reaches
_INDEX_LEAVES = ("iq", "ik", "iknorm", "iw")


def _index_loss(scores, select, probs):
    """``(L_I, dL_I/dI)``: ``L_I = mean_t KL(p[t] || softmax of I[t] over
    the selected set)``, and its gradient in the scores from the same
    float32 passes, ``(softmax over the selected set of I - p) / n``
    over the ``n`` rows (the softmax times the row's sum of ``p``, which
    is 1 but for rounding, as the plain derivative has it)."""
    keep = select != 0
    masked = jnp.where(keep, scores, -jnp.inf)
    logz = jax.nn.logsumexp(masked, axis=-1, keepdims=True)
    some = keep & (probs > 0)
    logp = jnp.log(jnp.where(some, probs, 1.0))
    rows = jnp.sum(jnp.where(some, probs * (logp - (scores - logz)), 0.0),
                   axis=-1)
    p = jnp.where(some, probs, 0.0)
    grad = (jnp.exp(masked - logz) * jnp.sum(p, axis=-1, keepdims=True)
            - p) / rows.size
    return jnp.mean(rows), grad


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _index_learned(run, leaves, operands):
    """The indexer's loss ``L_I``, ``run(leaves, operands, False)[0]``,
    whose whole backward runs in the forward pass: there ``run(leaves,
    operands, True)`` gives ``L_I`` with the leaves' gradients for a
    cotangent of 1, which are kept (``INDEX_GRAD_RESIDUAL``), and the
    backward hands the leaves the cotangent times them. The loss reaches
    nothing but the leaves — ``operands`` (the layer's detached input,
    the indexer's scores and their operands, the main attention's q, k
    and logsumexp, the selection) take no gradient — so that is the
    whole derivative, by linearity. ``remat`` keeps the gradients (2.2 M
    float32 a layer at 16 heads of 64 over a width of 2048) and its
    rebuilt forward makes nothing of the indexer: not the scores, not the
    head-summed distribution, not the loss's float32 passes over the
    positions x positions squares."""
    return run(leaves, operands, False)[0]


def _index_learned_fwd(run, leaves, operands):
    loss, grads = run(leaves, operands, True)
    return loss, checkpoint_name(grads, INDEX_GRAD_RESIDUAL)


def _index_learned_bwd(run, grads, ct):
    return jax.tree_util.tree_map(lambda g: ct * g, grads), None


_index_learned.defvjp(_index_learned_fwd, _index_learned_bwd)


@register_layer("gqa")
class GroupedQueryAttentionLayer(Layer):
    """Causal self-attention with grouped key/value heads, a head size
    of its own, an optional window, rotary on part of the head with a
    plain or YaRN table or by three position rows, an optional RMS norm
    on each head's q and k, a per-head output gate, and an optional
    learned indexer that picks the keys a query attends, on a sequence
    node (E,S,1) -> (E,S,1). With x a position's vector, no bias
    anywhere:

      q = x W_q (``nhead`` heads of ``head_dim``); k = x W_k, v = x W_v
      (``nkvhead`` heads); ``qk_norm = 1``: each head's q and k through
      an RMS norm over its ``head_dim`` features (``eps``), one gain
      vector for q and one for k; rotary on q and k (below)
      query head h attends key/value head h // (nhead / nkvhead)
      scores = q.k / sqrt(head_dim), causal; ``window = W`` lets
      position i see j with i - W < j <= i (0: every j <= i)
      o_h = softmax(scores) v
      ``head_gate = 1``: o_h <- sigmoid(x w_g[:, h]) o_h, one scalar a
      head and position from the layer's input (the head-wise form of
      arXiv:2505.06708)
      y = concat_h(o_h) W_o

    The layer knows only the heads the conf states: a chip's share of a
    layer's heads is a conf with fewer of them, and ``W_o`` then gives
    the held heads' partial sum.

    Rotary, on halves (``rotate_half``), over the first ``rotary_dim``
    features of a head (default: all; the others pass through) at
    ``rope_theta``; ``rope_type = yarn`` takes YaRN's frequency table
    (``ops.attention.rope_frequencies``) from ``rope_factor``,
    ``rope_original_max_position``, ``rope_beta_fast``,
    ``rope_beta_slow``, and cos and sin times ``rope_attention_factor``.
    ``mrope_section = a,b,c`` (the pairs of the rotated part, in three
    contiguous sections) lets the layer take a SECOND input node, (3,S,1):
    a token's temporal, height and width position, and pair i turns by
    the row of its section (``ops.attention.rope_sections``); with no
    second input the three rows are the token's index, which is text and
    the plain rotary.

    The indexer (``index_topk = K > 0``; DeepSeek sparse attention,
    arXiv:2512.02556 section 2.1, over grouped-query heads), on the
    layer's input DETACHED: ``qI = x W_qI`` (``index_heads`` heads of
    ``index_head_dim``), ``kI = LayerNorm(x W_kI)`` (one head), both
    rotated whole at ``rope_theta`` by the temporal position; ``w = x W_w
    / sqrt(index_heads index_head_dim)``; ``I[t,s] = sum_j w[t,j]
    relu(qI[t,j] . kI[s])`` in float32. Query t attends the K keys s <= t
    of largest I (all of them while t < K; a tie to the lower s), chosen
    exactly (``ops.attention.select_topk``: under ``flash`` one kernel,
    ``select_rows``, a block of rows resident in VMEM through all the
    counting passes; under ``ref`` XLA's passes over the whole square,
    ``select_topk_reference``), the same set forward, in the
    rebuilt forward under ``remat`` (the set is kept, not made again) and
    backward. The indexer learns from the main attention alone: with
    ``p[t,s] = sum_h a_h[t,s] / nhead`` detached, ``L_I = mean_t KL(p ||
    softmax over the selected set of I)``, and ``index_loss_coef L_I``
    joins the objective through the state's ``_aux_loss`` (0: no loss is
    built and the indexer's leaves get no gradient). Nothing but the
    indexer's leaves gets a gradient from it, so its whole backward runs
    in the forward pass, once (:func:`_index_learned`): the loss and its
    gradient in the scores from the same float32 passes, then the
    scores' own backward — under ``flash`` one kernel
    (``index_scores_bwd``, every causal tile once, at the score kernel's
    blocks of 512), under ``ref`` XLA's derivative of
    ``index_scores_reference`` — and the projections'; the backward
    scales the kept gradients by the loss's cotangent and hands them on
    only together with the layer's input's (``_gradients_together``).
    The selection log records ``index_grad: gqa.forward`` for such a
    layer. The state's ``dsa_stats``
    (``DSA_STATS``) count the pairs selected, ``L_I``, and the score
    tiles the kernels executed of a head's square.

    ``attn_impl`` in {auto, ref, flash}, as ``mla`` has it: ``ref`` runs
    on XLA's dots (the tests' oracle), ``flash`` is the Pallas kernel —
    k and v are read as they stand by the query heads of a group, with a
    window the tiles outside the band are neither computed nor fetched,
    and under a selection (``flash_attention_select``) neither are the
    tiles without a selected pair — at square blocks of the largest of
    1024 (a full or sparse layer; PERF.md section 6, PR 28/29) or 512 (a
    window layer; section 6, PR 32), 256, 128 that divides the
    positions. ``auto`` is the kernel on a TPU where such a block
    exists, else ``ref``. Under ``remat = 1`` the model keeps the
    kernel's output and logsumexp, a selection and the indexer's
    gradients, and rebuilds the rest: nothing of the indexer."""
    has_params = True

    _INT = ("nhead", "nkvhead", "head_dim", "window", "head_gate",
            "rotary_dim", "qk_norm", "index_heads", "index_head_dim",
            "index_topk")
    _FLOAT = ("rope_theta", "rope_factor", "rope_original_max_position",
              "rope_beta_fast", "rope_beta_slow", "rope_attention_factor",
              "eps", "index_loss_coef")

    def set_param(self, name, val):
        if name in self._INT:
            setattr(self, name, int(val))
        elif name in self._FLOAT:
            setattr(self, name, float(val))
        elif name == "mrope_section":
            self.mrope_section = tuple(int(v) for v in val.split(","))
        elif name == "rope_type":
            if val not in ("default", "yarn"):
                raise ValueError(f"unknown gqa rope_type {val!r}")
            self.rope_type = val
        elif name == "attn_impl":
            if val not in ("auto", "ref", "flash"):
                raise ValueError(f"unknown gqa attn_impl {val!r}")
            self.attn_impl = val

    def __init__(self, spec, global_cfg):
        self.nhead = self.nkvhead = self.head_dim = 0
        self.window = self.head_gate = self.rotary_dim = 0
        self.qk_norm = 0
        self.eps = 1e-6
        self.mrope_section = ()
        self.index_heads = self.index_head_dim = self.index_topk = 0
        self.index_loss_coef = 1.0
        self.attn_impl = "auto"
        self.rope_type = "default"
        self.rope_theta = 10000.0
        self.rope_factor = self.rope_attention_factor = 1.0
        self.rope_original_max_position = 0.0
        self.rope_beta_fast, self.rope_beta_slow = 32.0, 1.0
        super().__init__(spec, global_cfg)
        for k in ("nhead", "head_dim"):
            if getattr(self, k) <= 0:
                raise ValueError(f"gqa layer {spec.name!r} needs {k}")
        self.nkvhead = self.nkvhead or self.nhead
        self.rotary_dim = self.rotary_dim or self.head_dim
        if self.nhead % self.nkvhead:
            raise ValueError(f"gqa {spec.name!r}: {self.nhead} query heads "
                             f"over {self.nkvhead} key/value heads")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"gqa {spec.name!r}: rotary_dim must be even "
                             "and at most head_dim")
        if self.window < 0:
            raise ValueError(f"gqa {spec.name!r}: window must be >= 0")
        yarn = None
        if self.rope_type == "yarn":
            if self.rope_original_max_position <= 0:
                raise ValueError(f"gqa {spec.name!r}: rope_type = yarn "
                                 "needs rope_original_max_position")
            yarn = (self.rope_factor, self.rope_original_max_position,
                    self.rope_beta_fast, self.rope_beta_slow,
                    self.rope_attention_factor)
        self.rope_freqs, self.rope_mscale = rope_frequencies(
            self.rotary_dim, self.rope_theta, yarn)
        if self.mrope_section and (
                len(self.mrope_section) != 3 or yarn is not None
                or 2 * sum(self.mrope_section) != self.rotary_dim):
            raise ValueError(f"gqa {spec.name!r}: mrope_section is three "
                             "counts of pairs that make up rotary_dim, "
                             "under the plain table")
        if self.index_topk:
            if self.window or self.index_heads <= 0 \
                    or self.index_head_dim <= 0 or self.index_head_dim % 2:
                raise ValueError(
                    f"gqa {spec.name!r}: an indexer needs index_heads and "
                    "an even index_head_dim, and no window")
            self.index_freqs, _ = rope_frequencies(self.index_head_dim,
                                                   self.rope_theta)

    def _block(self, positions):
        """The kernel's square block at ``positions``, 0 where none
        divides them."""
        return flash_block(positions, 512 if self.window else 1024)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, len(in_shapes), 1)
        S = in_shapes[0][1]
        if len(in_shapes) > 1 and (
                not self.mrope_section or len(in_shapes) > 2
                or tuple(in_shapes[1]) != (3, S, 1)):
            raise ValueError(
                f"gqa {self.name!r}: a second input is the positions' "
                f"three rows, a (3,{S},1) node, under mrope_section")
        if not self.index_topk:
            # a sparse layer's tiles depend on what it selects: they are
            # counted as it runs (``dsa_stats``), not as it is built
            publish_flash_tiles(self.name, S, self._block(S),
                                self.window or None)
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        e = in_shapes[0][0]
        h, hkv, d = self.nhead, self.nkvhead, self.head_dim
        ks = jax.random.split(key, 5)
        w = self.hp.init_weight
        p = {"q": {"wmat": w(ks[0], (e, h, d), e, h * d)},
             "k": {"wmat": w(ks[1], (e, hkv, d), e, hkv * d)},
             "v": {"wmat": w(ks[2], (e, hkv, d), e, hkv * d)},
             "o": {"wmat": w(ks[3], (h, d, e), h * d, e)}}
        if self.head_gate:
            p["gate"] = {"wmat": w(ks[4], (e, h), e, h)}
        if self.qk_norm:
            p["qnorm"] = {"gamma": jnp.ones((d,), jnp.float32)}
            p["knorm"] = {"gamma": jnp.ones((d,), jnp.float32)}
        if self.index_topk:
            j, di = self.index_heads, self.index_head_dim
            ki = [jax.random.fold_in(key, 5 + n) for n in range(3)]
            p["iq"] = {"wmat": w(ki[0], (e, j, di), e, j * di)}
            p["ik"] = {"wmat": w(ki[1], (e, di), e, di)}
            p["iknorm"] = {"gamma": jnp.ones((di,), jnp.float32),
                           "beta": jnp.zeros((di,), jnp.float32)}
            p["iw"] = {"wmat": w(ki[2], (e, j), e, j)}
        return p

    def init_state(self, in_shapes):
        if not self.index_topk:
            return {}
        return {"dsa_stats": jnp.zeros((len(DSA_STATS),), jnp.float32),
                "_aux_loss": jnp.zeros((), jnp.float32)}

    def param_pspecs(self):
        qkv = {"wmat": (None, "model", None)}
        return {"q": qkv, "k": qkv, "v": qkv,
                "gate": {"wmat": (None, "model")},
                "o": {"wmat": ("model", None, None)}}

    def _impl(self, positions):
        """``(flash | ref, the kernel's block)``: ``auto`` is the kernel
        on a TPU where a block divides the positions."""
        impl, blk = self.attn_impl, self._block(positions)
        if impl == "auto":
            impl = "flash" if jax.default_backend() == "tpu" and blk \
                else "ref"
        return impl, blk

    def _attend(self, q, k, v):
        from ..ops.fused import note_attention
        S = q.shape[1]
        window = self.window or None
        impl, blk = self._impl(S)
        note_attention("gqa.flash_window" if impl == "flash" and window
                       else "gqa." + impl)
        if impl == "ref":
            return attention_reference(q, k, v, causal=True, window=window)
        if not blk:
            raise ValueError(f"gqa {self.name!r}: no flash block divides "
                             f"{S} positions")
        return flash_attention(q, k, v, True, None, blk, blk, None, window)

    def _attend_sparse(self, q, k, v, select):
        """The main attention over the selected pairs -> ``(o, the
        kernel's logsumexp or None under ``ref``)``."""
        from ..ops.fused import note_attention
        S = q.shape[1]
        impl, blk = self._impl(S)
        note_attention("gqa.flash_sparse" if impl == "flash"
                       else "gqa.ref_sparse")
        if impl == "ref":
            return attention_reference(q, k, v, causal=True,
                                       select=select), None
        if not blk:
            raise ValueError(f"gqa {self.name!r}: no flash block divides "
                             f"{S} positions")
        return flash_attention_select(q, k, v, select, None, blk, blk)

    def _index_block(self, positions):
        """The score kernel's block at ``positions``; 0 where XLA's form
        runs (``ref``, or no block of 512 or less divides them)."""
        blk = flash_block(positions, 512)
        return blk if self._impl(positions)[0] == "flash" else 0

    def _index(self, params, x, pos, cd):
        """The indexer's scores (B, S, S) float32 from the layer's
        detached input."""
        return self._scores(*self._index_parts(params, x, pos, cd))

    def _scores(self, qi, ki, wt):
        """The indexer's scores from its parts: the kernel at its block,
        else XLA's form."""
        blk = self._index_block(qi.shape[1])
        return index_scores(qi, ki, wt, blk) if blk \
            else index_scores_reference(qi, ki, wt)

    def _index_parts(self, params, x, pos, cd):
        """What the indexer's scores are made of, from the layer's
        detached input: ``qI`` (B, S, J, d) and ``kI`` (B, S, d) rotated,
        in ``cd``, and the heads' weights ``w`` (B, S, J) float32."""
        x = jax.lax.stop_gradient(x)
        w = lambda nm: params[nm]["wmat"].astype(cd)
        qi = jnp.einsum("bse,ejd->bsjd", x, w("iq"))
        ki = jnp.einsum("bse,ed->bsd", x, w("ik")).astype(jnp.float32)
        mu = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mu), axis=-1, keepdims=True)
        ki = (ki - mu) * jax.lax.rsqrt(var + self.eps) \
            * params["iknorm"]["gamma"] + params["iknorm"]["beta"]
        ki = ki.astype(cd)[:, :, None, :]
        if pos is None:
            qi, ki = (rope_partial(a, self.index_freqs) for a in (qi, ki))
        else:                  # the whole head by the temporal row
            rows = jnp.broadcast_to(pos[:, :1], (pos.shape[0], 1,
                                                 pos.shape[2]))
            half = (self.index_head_dim // 2,)
            qi, ki = (rope_sections(a, self.index_freqs, rows, half)
                      for a in (qi, ki))
        wt = jnp.einsum("bse,ej->bsj", x, w("iw"),
                        preferred_element_type=jnp.float32) \
            * (self.index_heads * self.index_head_dim) ** -0.5
        return qi, ki[:, :, 0], wt

    def _learn(self, leaves, operands, with_grads):
        """``(L_I, the leaves' gradients of L_I or None)``: the forward
        rule of :func:`_index_learned`, which also makes the head-summed
        target. ``operands`` = the layer's input in the products' dtype,
        its position rows or None, the indexer's parts and scores (from
        the detached leaves: the selection was made from them), the main
        attention's q, k and logsumexp (None under ``ref``), and the
        selection."""
        x, pos, parts, scores, q, k, lse, select = operands
        S = x.shape[1]
        with jax.named_scope("gqa.attend.sparse"):
            probs = head_sum_probs_reference(q, k, select) if lse is None \
                else head_sum_probs(q, k, lse, select, None, self._block(S))
        with jax.named_scope("gqa.index_loss"):
            loss, d_scores = _index_loss(scores, select, probs)
        if not with_grads:
            return loss, None
        with jax.named_scope("gqa.index"):
            _, pull = jax.vjp(
                lambda lv: self._index_parts(lv, x, pos, x.dtype), leaves)
            blk = self._index_block(S)
            d_parts = index_scores_backward(*parts, d_scores, blk) if blk \
                else jax.vjp(index_scores_reference, *parts)[1](d_scores)
            return loss, pull(d_parts)[0]

    def _select(self, scores):
        """The selection (B, S, S) int8 of the indexer's scores: the
        kernel (``select_rows``) under ``flash`` where a row block
        divides the positions, else XLA's counting passes."""
        from ..ops.fused import note_select
        S = scores.shape[1]
        kernel = self._impl(S)[0] == "flash" and select_rows_block(S) > 0
        note_select("gqa.select_rows" if kernel else "gqa.select_ref")
        return select_topk(scores, self.index_topk, kernel)

    def select(self, params, x):
        """The selection (B, S, S) int8 the layer makes for its normed
        input ``x`` (B, S, E) at text positions, products in ``x``'s
        dtype: the function ``apply`` runs, for a caller that holds an
        input of its own (the benchmark's reference does)."""
        return self._select(self._index(params, x, None, x.dtype))

    def rotate(self, a, pos=None):
        """The main heads' rotary on (B, S, H, head_dim): by the three
        position rows ``pos`` (B, 3, S), or by the token's index."""
        if pos is None:
            return rope_partial(a, self.rope_freqs, self.rope_mscale)
        return rope_sections(a, self.rope_freqs, pos, self.mrope_section)

    def apply(self, params, state, inputs, ctx):
        if ctx.seq_axis is not None:
            raise ValueError("gqa has no sequence-parallel path")
        cd = ctx.compute_dtype
        x = _seq(inputs[0]).astype(cd)
        learn = bool(self.index_topk and ctx.train and self.index_loss_coef)
        if learn:
            x, leaves = _gradients_together(
                (x, {nm: params[nm] for nm in _INDEX_LEAVES}))
        w = lambda nm: params[nm]["wmat"].astype(cd)
        pos = None
        if len(inputs) > 1:          # (b, S, 1, 3) -> the rows (b, 3, S)
            pos = inputs[1].reshape(x.shape[0], x.shape[1], 3) \
                .transpose(0, 2, 1)
        with jax.named_scope("gqa.proj"):
            q, k, v = (jnp.einsum("bse,ehd->bshd", x, w(nm))
                       for nm in ("q", "k", "v"))
            if self.qk_norm:
                q, k = (rms_normalize(a, params[nm]["gamma"],
                                      self.eps).astype(cd)
                        for a, nm in ((q, "qnorm"), (k, "knorm")))
            q, k = self.rotate(q, pos), self.rotate(k, pos)
        if self.index_topk:
            with jax.named_scope("gqa.index"):
                # learning, the leaves' gradients come from _index_learned
                parts = self._index_parts(
                    jax.lax.stop_gradient(params) if learn else params,
                    x, pos, cd)
                scores = self._scores(*parts)
            with jax.named_scope("gqa.select"):
                select = checkpoint_name(self._select(scores),
                                         SELECT_RESIDUAL)
            with jax.named_scope("gqa.attend.sparse"):
                o, lse = self._attend_sparse(q, k, v, select)
            if learn:
                from ..ops.fused import note_index_grad
                note_index_grad("gqa.forward")
                loss = _index_learned(
                    self._learn, leaves, jax.lax.stop_gradient(
                        (x, pos, parts, scores, q, k, lse, select)))
            else:
                loss = jnp.zeros((), jnp.float32)
            with jax.named_scope("gqa.select"):
                blk = self._block(x.shape[1])
                tiles = jnp.sum(select_tiles(select, blk, blk)[0] > 0) \
                    / x.shape[0] if blk else 0.0
                stats = jnp.stack([
                    jnp.sum(select, dtype=jnp.float32), loss,
                    jnp.asarray(tiles, jnp.float32),
                    jnp.float32((x.shape[1] // blk) ** 2 if blk else 0)])
            state = {"dsa_stats": jax.lax.stop_gradient(stats),
                     "_aux_loss": self.index_loss_coef * loss}
        else:
            with jax.named_scope("gqa.attend.window" if self.window
                                 else "gqa.attend.full"):
                o = self._attend(q, k, v)
        if self.head_gate:
            with jax.named_scope("gqa.gate"):
                g = jax.nn.sigmoid(jnp.einsum(
                    "bse,eh->bsh", x, w("gate")).astype(jnp.float32))
                o = (o.astype(jnp.float32) * g[..., None]).astype(cd)
        with jax.named_scope("gqa.proj"):
            y = jnp.einsum("bshd,hde->bse", o, w("o"))
        return [_unseq(y)], state


@register_layer("dsa")
class SparseAttentionLayer(GroupedQueryAttentionLayer):
    """``gqa`` with its indexer on, under a kind of its own name: a conf
    that means a selected set names ``dsa``, and a program that has no
    such attention refuses the conf as the net is built (``gqa`` takes
    the same keys, but ``set_param`` passes over a key it does not know,
    and an older ``gqa`` would train a dense net from it)."""

    def __init__(self, spec, global_cfg):
        super().__init__(spec, global_cfg)
        if self.index_topk <= 0:
            raise ValueError(f"dsa layer {spec.name!r} needs index_topk")


@register_layer("shortconv")
class ShortConvLayer(Layer):
    """The gated short convolution of the LFM2 family, which mixes
    positions in place of attention, causal, on a sequence node (E,S,1)
    -> (E,S,1). With x a position's vector, no bias anywhere:

      [B ; C ; x~] = x W_in        W_in (E, 3, E): B, C, x~ in that order
      u[t] = B[t] * x~[t]
      v[t] = sum_{i<L} w[i] * u[t - (L-1) + i]      u[<0] = 0; w (L, E)
      z[t] = C[t] * v[t]
      y    = z W_out               W_out (E, E)

    ``conv_L_cache`` = L, the taps of the depthwise convolution (3 in the
    published configurations). The two projections run on the MXU under
    the scope ``shortconv.proj``; the gates and the convolution — L
    shifted multiply-adds over the channels in float32, which XLA fuses
    into one pass — under ``shortconv.mix``. The taps' gradient is a
    reduction over the positions. Under ``remat = 1`` the layer keeps
    nothing but its input and is rebuilt whole in the backward pass."""
    has_params = True

    def set_param(self, name, val):
        if name == "conv_L_cache":
            self.taps = int(val)

    def __init__(self, spec, global_cfg):
        self.taps = 3
        super().__init__(spec, global_cfg)
        if self.taps < 1:
            raise ValueError(f"shortconv {spec.name!r}: conv_L_cache must "
                             "be at least 1")

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        e = in_shapes[0][0]
        ks = jax.random.split(key, 3)
        w = self.hp.init_weight
        return {"in_proj": {"wmat": w(ks[0], (e, 3, e), e, 3 * e)},
                "conv": {"wmat": w(ks[1], (self.taps, e), self.taps, 1)},
                "out_proj": {"wmat": w(ks[2], (e, e), e, e)}}

    def apply(self, params, state, inputs, ctx):
        if ctx.seq_axis is not None:
            raise ValueError("shortconv has no sequence-parallel path")
        cd = ctx.compute_dtype
        x = _seq(inputs[0]).astype(cd)
        S = x.shape[1]
        with jax.named_scope("shortconv.proj"):
            bcx = jnp.einsum("bse,ekf->bskf", x,
                             params["in_proj"]["wmat"].astype(cd))
        with jax.named_scope("shortconv.mix"):
            gate_b, gate_c, xt = (bcx[:, :, i].astype(jnp.float32)
                                  for i in range(3))
            u = gate_b * xt
            w = params["conv"]["wmat"].astype(jnp.float32)
            v = 0.0
            for i in range(self.taps):
                lag = self.taps - 1 - i
                shifted = u if not lag else jnp.pad(
                    u, ((0, 0), (lag, 0), (0, 0)))[:, :S]
                v = v + w[i] * shifted
            z = (gate_c * v).astype(cd)
        with jax.named_scope("shortconv.proj"):
            y = jnp.einsum("bsf,fe->bse", z,
                           params["out_proj"]["wmat"].astype(cd))
        return [_unseq(y)], state


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x W_g) * (x W_u)) W_d`` on (..., E); no bias."""
    h = jax.nn.silu(jnp.einsum("...e,ef->...f", x, w_gate)) \
        * jnp.einsum("...e,ef->...f", x, w_up)
    return jnp.einsum("...f,fe->...e", h, w_down)


@register_layer("ffn")
class FFNLayer(Layer, _SeqLinearMixin):
    """Position-wise feed-forward (E,S,1) -> (E,S,1); ``nhidden`` = inner
    dim, ``act`` in {gelu, relu, swiglu}. ``swiglu`` is the gated form
    ``(silu(x W_g) * (x W_h)) W_o`` with a third matrix ``g`` and no
    bias. TP: inner dim sharded over 'model'."""
    has_params = True

    def set_param(self, name, val):
        if name == "act":
            if val not in ("gelu", "relu", "swiglu"):
                raise ValueError(f"unknown ffn act {val!r}")
            self.act = val

    def __init__(self, spec, global_cfg):
        self.act = "gelu"
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        e = in_shapes[0][0]
        f = self.hp.num_hidden or 4 * e
        k1, k2 = jax.random.split(key)
        if self.act == "swiglu":
            k1, k3 = jax.random.split(k1)
            return {"g": self._linear_params(k3, e, f, True),
                    "h": self._linear_params(k1, e, f, True),
                    "o": self._linear_params(k2, f, e, True)}
        return {"h": self._linear_params(k1, e, f, self.hp.no_bias),
                "o": self._linear_params(k2, f, e, self.hp.no_bias)}

    def param_pspecs(self):
        gate = {"g": {"wmat": (None, "model")}} \
            if self.act == "swiglu" else {}
        return {"h": {"wmat": (None, "model"), "bias": ("model",)},
                "o": {"wmat": ("model", None), "bias": None}, **gate}

    def apply(self, params, state, inputs, ctx):
        x = _seq(inputs[0]).astype(ctx.compute_dtype)
        if self.act == "swiglu":
            return [_unseq(swiglu(
                x, *(params[k]["wmat"].astype(ctx.compute_dtype)
                     for k in ("g", "h", "o"))))], state
        h = jnp.einsum("bse,ef->bsf", x,
                       params["h"]["wmat"].astype(ctx.compute_dtype))
        if "bias" in params["h"]:
            h = h + params["h"]["bias"].astype(ctx.compute_dtype)
        h = jax.nn.gelu(h) if self.act == "gelu" else jax.nn.relu(h)
        y = jnp.einsum("bsf,fe->bse", h,
                       params["o"]["wmat"].astype(ctx.compute_dtype))
        if "bias" in params["o"]:
            y = y + params["o"]["bias"].astype(ctx.compute_dtype)
        return [_unseq(y)], state


@register_layer("seqfc")
class SeqFCLayer(Layer, _SeqLinearMixin):
    """Per-position linear projection (E,S,1) -> (K,S,1), e.g. the LM head.
    ``nhidden`` = K."""
    has_params = True

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        e, s, _ = in_shapes[0]
        return [(self.hp.num_hidden, s, 1)]

    def init_params(self, key, in_shapes):
        e = in_shapes[0][0]
        return self._linear_params(key, e, self.hp.num_hidden, self.hp.no_bias)

    def param_pspecs(self):
        return {"wmat": (None, "model"), "bias": ("model",)}

    def apply(self, params, state, inputs, ctx):
        x = _seq(inputs[0])
        if "wmat_scale" in params:
            # PTQ-derived int8 weights (quant/ptq.py): positions fold
            # into rows so the projection runs as one int8 matmul with
            # the dequant/bias epilogue (ops/quant.py)
            from ..ops.quant import int8_matmul
            b, s, e = x.shape
            y2 = int8_matmul(x.reshape(b * s, e), params["wmat"],
                             params["wmat_scale"], params["act_scale"],
                             params.get("bias"), "none")
            return [_unseq(y2.reshape(b, s, -1))], state
        x = x.astype(ctx.compute_dtype)
        y = jnp.einsum("bse,ek->bsk", x,
                       params["wmat"].astype(ctx.compute_dtype))
        if "bias" in params:
            y = y + params["bias"].astype(ctx.compute_dtype)
        return [_unseq(y)], state


@register_layer("add")
class AddLayer(Layer):
    """Elementwise sum of N same-shape nodes (residual connections).
    The DAG dialect already allows one node to feed several layers (the
    functional executor has no buffer aliasing), so x + f(x) is
    ``layer[x,fx->y] = add``."""

    def infer_shapes(self, in_shapes):
        if len(in_shapes) < 2 or len(self.spec.nindex_out) != 1:
            raise ValueError(f"add layer {self.name!r} needs >=2 inputs, 1 output")
        for s in in_shapes[1:]:
            if s != in_shapes[0]:
                raise ValueError(
                    f"add layer {self.name!r}: shape mismatch {in_shapes}")
        return [in_shapes[0]]

    def apply(self, params, state, inputs, ctx):
        out = inputs[0]
        for x in inputs[1:]:
            out = out + x
        return [out], state


@register_layer("label_ids")
class LabelIdsLayer(Layer):
    """The label slice named by ``target`` as a flat id node (1,1,S), for
    a head that reads the row's labels as an input: the
    multi-token-prediction module embeds the next token, which is the
    label at the same position. Its graph input (any node; the data node
    by convention) only orders it. Where a pass has no label (inference)
    it emits zeros: such a head is a training-time head."""

    def set_param(self, name, val):
        if name == "target":
            self.target = val

    def __init__(self, spec, global_cfg):
        self.target = "label"
        super().__init__(spec, global_cfg)

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def apply(self, params, state, inputs, ctx):
        lab = (ctx.labels or {}).get(self.target)
        x = inputs[0]
        if lab is None:
            return [jnp.zeros_like(x)], state
        return [lab.reshape(x.shape).astype(x.dtype)], state


@register_layer("lmloss")
class LMLossLayer(LossLayerBase):
    """Per-token softmax cross-entropy for language modeling: logits node
    (V,S,1) vs a label slice of width S (token ids). Forward emits per-token
    **log**-probabilities (log_softmax: numerically exact where probs would
    underflow f32, so confidently-wrong tokens keep their gradient; argmax
    metrics are unaffected); loss = masked mean NLL over all tokens.

    ``shift = k`` (default 0) scores position i against the label at
    i + k and leaves the row's last k positions out of the mean: a
    multi-token-prediction head of depth k over the same label row.
    ``grad_scale`` weighs the head in the objective.

    ``metric_stats``: what a train metric needs of this node, reduced on
    the device — per row (summed log-probability of the labels, argmax
    hits, positions counted) — so that the loop fetches three numbers a
    row instead of S x V log-probabilities."""

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "shift":
            self.shift = int(val)

    def __init__(self, spec, global_cfg):
        self.shift = 0
        super().__init__(spec, global_cfg)
        if self.shift < 0:
            raise ValueError("lmloss: shift must be >= 0")

    def apply(self, params, state, inputs, ctx):
        x = inputs[0]                              # (b, S, 1, V)
        logits = x.astype(jnp.float32)
        with jax.named_scope("head_loss"):
            return [jax.nn.log_softmax(logits, axis=-1)], state

    def _aligned(self, outputs, label):
        logp_all = outputs[0]                      # (b, S, 1, V) log-probs
        b, S = logp_all.shape[0], logp_all.shape[1]
        lp2 = logp_all.reshape(b, S, -1)
        idx = label.astype(jnp.int32)              # (b, S)
        if self.shift:
            lp2, idx = lp2[:, :S - self.shift], idx[:, self.shift:]
        return lp2, idx

    def loss(self, outputs, label, mask):
        with jax.named_scope("head_loss"):
            lp2, idx = self._aligned(outputs, label)
            logp = jnp.take_along_axis(lp2, idx[:, :, None], axis=2)[:, :, 0]
            per_example = -jnp.mean(logp, axis=1)      # mean over tokens
            return self._mean(per_example, mask)

    def metric_stats(self, outputs, label):
        with jax.named_scope("head_loss"):
            lp2, idx = self._aligned(outputs, label)
            logp = jnp.take_along_axis(lp2, idx[:, :, None], axis=2)[:, :, 0]
            hits = (jnp.argmax(lp2, axis=2) == idx).astype(jnp.float32)
            count = jnp.full((lp2.shape[0],), float(lp2.shape[1]),
                             jnp.float32)
            return jnp.stack([jnp.sum(logp, axis=1), jnp.sum(hits, axis=1),
                              count], axis=1)
