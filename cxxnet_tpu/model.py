"""Network: compile a NetGraph into a pure, jittable forward function.

TPU-native replacement for the reference's NeuralNet<xpu> DAG executor
(/root/reference/src/nnet/neural_net-inl.hpp:23-318). The reference allocates
per-device Node buffers, runs layer->Forward over connections in order, and
hand-written layer->Backprop in reverse (activations doubling as gradient
storage). Here the whole graph is one pure function of (params, state, batch):
node values are a functional list, losses are summed into a scalar, and
``jax.grad`` of that scalar reproduces every hand-written backward pass.
Shared layers (kSharedLayer weight tying, neural_net-inl.hpp:259-265) reuse
the primary layer's parameter subtree.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ConfigPairs, Policy
from .graph import NetGraph, global_param, policy_from_config
from .layers import ApplyCtx, Layer, create_layer
from .layers.base import Shape3, is_flat, to_nhwc
from .ops.attention import (FLASH_RESIDUALS, INDEX_GRAD_RESIDUAL,
                            SELECT_RESIDUAL)
from .ops.fused import selection_site

#: ``remat = 1`` rebuilds a layer's activations in the backward pass but
#: for the values named here: the flash kernel's output and logsumexp,
#: which its backward needs and only the kernel's forward makes, a
#: sparse layer's selection (one int8 a pair: kept, the rebuilt forward
#: neither runs the selection again nor can pick another set than the
#: forward attended; PERF.md section 6, PR 34 has the chip A/B against
#: rebuilding it), and that layer's indexer's gradients, made in the
#: forward pass (its leaves' size: kept, the rebuilt forward makes
#: neither the indexer's scores nor the head-summed distribution nor the
#: loss's passes over those positions x positions squares). A layer
#: that never reaches the kernel has no such name and keeps nothing, as
#: under a bare ``jax.checkpoint``
_REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    *FLASH_RESIDUALS, SELECT_RESIDUAL, INDEX_GRAD_RESIDUAL)

Params = Dict[str, Dict[str, jax.Array]]
NetState = Dict[str, Any]

_SCOPE_UNSAFE = re.compile(r"[^A-Za-z0-9_.\-]")


def layer_scope(name: str):
    """``jax.named_scope`` of one graph layer: every op the layer
    traces carries ``.../<name>/...`` in its ``op_name`` — forward under
    ``jvp(..)``, backward under ``transpose(jvp(..))`` — which is how a
    device trace is attributed to layers
    (telemetry/traceparse.classify). Metadata only."""
    return jax.named_scope(_SCOPE_UNSAFE.sub("_", name))


@dataclasses.dataclass
class ForwardResult:
    loss: jax.Array                       # scalar total loss
    state: NetState                       # updated layer state (BN stats, ...)
    nodes: Optional[Dict[str, jax.Array]]  # node name -> value (if captured)
    out: jax.Array                        # value of the last node (predictions)
    # per-layer activation health stats (model-health probe; None unless
    # apply(health=True)): {layer: {"absmax", "zero_frac"?, "bn_var_min"?}}
    health: Optional[Dict[str, Any]] = None
    # node name -> a loss head's device-side reduction of that node for
    # the train metric (``LMLossLayer.metric_stats``); only where the
    # whole label was handed in
    metric_stats: Optional[Dict[str, jax.Array]] = None


class Network:
    """Static graph + layer objects; all runtime data flows through apply."""

    def __init__(self, graph: NetGraph, cfg: ConfigPairs):
        self.graph = graph
        if graph.input_shape is None:
            raise ValueError("input_shape must be set")
        # mixed-precision policy: fp32 master params/outputs, activations
        # and gradients in compute_dtype (config.Policy); per-layer casts
        # happen at apply time inside jit so XLA fuses them
        self.policy: Policy = policy_from_config(cfg)
        self.compute_dtype = self.policy.compute_dtype
        # remat = 1: rematerialize each layer's activations in the backward
        # pass (jax.checkpoint) — trades FLOPs for HBM, the standard TPU
        # recipe for memory-bound models (no reference analog; the closest
        # is temp_col_max's memory/compute staging, SURVEY §5)
        self.remat = bool(int(global_param(cfg, "remat", "0")))
        # (site, kind) -> which implementation it took
        # (ops.fused.SelectionLog): written while apply() is traced,
        # printed once by the trainer
        self.fused_log: Dict[Tuple[str, str], str] = {}
        self._tp_plan_logged = False
        # rule-driven sharding (parallel/rules.py): the validated
        # config namespace (partition_rules / fsdp_*), custom rules
        # prepended to the generated per-model table
        from .graph import sharding_from_config
        self.sharding_cfg = sharding_from_config(cfg)
        self._rule_pspecs_cache = None
        self._param_shapes_cache = None
        # build layer objects; shared specs reuse the primary object
        self.layers: List[Layer] = []
        for spec in graph.layers:
            if spec.is_shared:
                self.layers.append(self.layers[spec.primary_layer_index])
            else:
                self.layers.append(create_layer(spec, graph.defcfg))
        # shape inference over the DAG (reference InitNet/InitConnection)
        self.node_shapes: List[Optional[Shape3]] = [None] * graph.num_nodes
        self.node_shapes[0] = graph.input_shape
        for i in range(graph.extra_data_num):
            self.node_shapes[1 + i] = graph.extra_shapes[i]
        self.layer_out_shapes: List[List[Shape3]] = []
        for li, (spec, layer) in enumerate(zip(graph.layers, self.layers)):
            in_shapes = []
            for ni in spec.nindex_in:
                if self.node_shapes[ni] is None:
                    raise ValueError(
                        f"layer {spec.name!r}: input node "
                        f"{graph.node_names[ni]!r} has no value yet")
                in_shapes.append(self.node_shapes[ni])
            out_shapes = layer.infer_shapes(in_shapes)
            self.layer_out_shapes.append(out_shapes)
            for ni, s in zip(spec.nindex_out, out_shapes):
                self.node_shapes[ni] = s
        self.loss_layers = [(li, l) for li, l in enumerate(self.layers)
                            if l.is_loss]
        self._in_shapes_of = [
            [self.node_shapes[ni] for ni in spec.nindex_in]
            for spec in graph.layers]
        # static activation-fold plan (graph.act_fusion_plan): producer
        # layers apply a following relu themselves and the folded relus
        # pass through in apply()
        from .graph import act_fusion_plan
        self._fuse_act, self._act_folded = act_fusion_plan(graph)
        # stem channel padding (graph.stem_pad_plan): value-exact, so on
        # by default; stem_pad = 0 disables, stem_pad = N (>= 2)
        # overrides the pad-to width (default 4 — lane/sublane-friendly
        # for the RGB stem and its space-to-depth fold). "1"/"on" mean
        # ON at the default width, matching input_fold's auto|1|0
        # grammar — a width of 1
        # could never pad anything and silently-off would invert the
        # user's intent.
        sp = global_param(cfg, "stem_pad", "auto").strip().lower()
        if sp in ("0", "off", "false", "no"):
            self._cin_pad = {}
        else:
            from .graph import stem_pad_plan
            pad_to = int(sp) if sp.isdigit() and int(sp) >= 2 else 4
            self._cin_pad = stem_pad_plan(graph, pad_to=pad_to)

    # -- init --------------------------------------------------------------
    def init(self, key: jax.Array) -> Tuple[Params, NetState]:
        """Initialize params + state (reference NeuralNet::InitModel,
        neural_net-inl.hpp:68-86; per-layer RNG keys replace the per-device
        seeded mshadow::Random)."""
        params: Params = {}
        state: NetState = {}
        for li, (spec, layer) in enumerate(zip(self.graph.layers, self.layers)):
            if spec.is_shared:
                continue
            in_shapes = self._in_shapes_of[li]
            if layer.has_params:
                params[layer.name] = layer.init_params(
                    jax.random.fold_in(key, li), in_shapes)
            st = layer.init_state(in_shapes)
            if st:
                state[layer.name] = st
        return params, state

    # -- forward -----------------------------------------------------------
    def apply(self,
              params: Params,
              state: NetState,
              data: jax.Array,
              label: Optional[jax.Array] = None,
              mask: Optional[jax.Array] = None,
              extra_data: Tuple[jax.Array, ...] = (),
              rng: Optional[jax.Array] = None,
              train: bool = False,
              capture_nodes: bool = False,
              seq_axis: Optional[str] = None,
              data_axis: Optional[str] = None,
              label_slices: Optional[Dict[Tuple[int, int],
                                          jax.Array]] = None,
              compute_dtype: Optional[Any] = None,
              health: bool = False) -> ForwardResult:
        """One forward pass. ``data`` is NHWC (batch, y, x, c) or flat
        (batch,1,1,n); ``label`` is (batch, label_width); ``mask`` is (batch,)
        marking real rows (None = all real). ``label_slices`` maps a loss
        layer's global label_vec range to its (pre-sliced) label array —
        used under sequence parallelism, where the full-width label cannot
        be sliced locally with global indices (each shard holds its own
        token-aligned columns of every slice). ``compute_dtype`` overrides
        the config policy's compute dtype for this call — the serve
        engine's per-engine ``dtype`` option (a checkpoint trained fp32
        can serve bf16 and vice versa; fp32 masters make the cast safe).
        ``health=True`` taps per-layer activation stats (abs-max,
        dead-ReLU zero fraction, BN batch-variance floor) into
        ``result.health`` through the ``ApplyCtx.health_sink`` hook —
        the model-health probe's in-trace activation view
        (telemetry/modelhealth.py); False adds zero ops."""
        g = self.graph
        batch = data.shape[0]
        nodes: List[Optional[jax.Array]] = [None] * g.num_nodes
        nodes[0] = data
        for i, ed in enumerate(extra_data):
            nodes[1 + i] = ed
        if mask is None:
            mask = jnp.ones((batch,), jnp.float32)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        new_state: NetState = dict(state)
        cdt = self.compute_dtype if compute_dtype is None else compute_dtype
        health_sink: Optional[Dict[str, Any]] = {} if health else None
        total_loss = jnp.zeros((), jnp.float32)
        metric_stats: Dict[str, jax.Array] = {}
        labels = None
        if label is not None:
            labels = {n: label[:, slice(*g.label_slice(n))]
                      for n in g.label_name_map}
        for li, (spec, layer) in enumerate(zip(g.layers, self.layers)):
            if li in self._act_folded:
                # relu folded into its producer's epilogue
                # (graph.act_fusion_plan): the producer already applied
                # it, so this layer is a pass-through — the tap still
                # fires (the node holds the post-activation value, so
                # the dead-ReLU fraction stays meaningful)
                nodes[spec.nindex_out[0]] = nodes[spec.nindex_in[0]]
                if health_sink is not None:
                    self._health_tap(health_sink, spec, nodes, train)
                continue
            ctx = ApplyCtx(train=train, rng=jax.random.fold_in(rng, li),
                           compute_dtype=cdt,
                           seq_axis=seq_axis, data_axis=data_axis,
                           fuse_act=self._fuse_act.get(li),
                           cin_pad=self._cin_pad.get(li),
                           health_sink=health_sink, labels=labels)
            inputs = [nodes[ni] for ni in spec.nindex_in]
            lparams = params.get(layer.name, {})
            lstate = new_state.get(layer.name, {})
            # every implementation choice this layer makes lands in
            # fused_log under its name (tracing is synchronous, so the
            # binding covers remat's inner trace too)
            with selection_site(self.fused_log, spec.name), \
                    layer_scope(spec.name):
                if self.remat and layer.has_params:
                    def _fn(lp, ls, rng_, *ins, _layer=layer, _ctx=ctx):
                        c = ApplyCtx(train=_ctx.train, rng=rng_,
                                     compute_dtype=_ctx.compute_dtype,
                                     seq_axis=_ctx.seq_axis,
                                     data_axis=_ctx.data_axis,
                                     fuse_act=_ctx.fuse_act,
                                     cin_pad=_ctx.cin_pad)
                        return _layer.apply(lp, ls, list(ins), c)
                    outputs, lstate_out = jax.checkpoint(
                        _fn, policy=_REMAT_POLICY)(
                        lparams, lstate, ctx.rng, *inputs)
                else:
                    outputs, lstate_out = layer.apply(lparams, lstate,
                                                      inputs, ctx)
            if lstate_out:
                new_state[layer.name] = lstate_out
                # auxiliary regularizers (e.g. MoE load-balancing loss)
                # ride the state dict under "_aux_loss" and only count
                # during training
                if train and "_aux_loss" in lstate_out:
                    total_loss = total_loss + lstate_out["_aux_loss"]
            for ni, out in zip(spec.nindex_out, outputs):
                nodes[ni] = out
            if health_sink is not None:
                self._health_tap(health_sink, spec, nodes, train)
            if layer.is_loss and (label is not None
                                  or label_slices is not None):
                a, b = g.label_slice(layer.target)
                with layer_scope(spec.name):
                    lab = (label_slices[(a, b)] if label_slices is not None
                           else label[:, a:b])
                    total_loss = total_loss + layer.loss(
                        outputs, lab.astype(jnp.float32), mask)
                    if label is not None and hasattr(layer, "metric_stats"):
                        metric_stats[g.node_names[spec.nindex_out[0]]] = \
                            layer.metric_stats(outputs, lab)
        node_map = None
        if capture_nodes:
            node_map = {name: nodes[i] for i, name in enumerate(g.node_names)
                        if nodes[i] is not None}
        # "last node" = output of the final layer (reference ForwardTo default
        # req = top node, nnet_impl-inl.hpp:203-216)
        out = nodes[g.layers[-1].nindex_out[0]] if g.layers else data
        return ForwardResult(loss=total_loss, state=new_state,
                             nodes=node_map, out=out, health=health_sink,
                             metric_stats=metric_stats)

    #: layer types whose exact-zero output fraction IS the dead-unit
    #: signal (a relu that emits 0 for every batch row is a dead unit;
    #: sustained growth of that fraction is the classic silent-failure
    #: mode the OPT run logs watched per layer)
    _HEALTH_DEAD_TYPES = frozenset({"relu"})

    def _health_tap(self, sink: Dict[str, Any], spec, nodes,
                    train: bool) -> None:
        """Per-layer activation stats for the model-health probe (all
        fp32 scalars, computed in-trace): abs-max for every layer,
        exact-zero output fraction for relus (dead-ReLU signal), and
        the minimum per-channel batch variance of a batch_norm layer's
        INPUT (train only — the quantity whose collapse toward 0 makes
        the BN rsqrt amplify noise). Padding rows are included in the
        batch statistics — a per-epoch tail effect too small to gate
        on. A plugin layer may have added its own entry via
        ``ctx.health_sink``; the standard taps win on a name clash."""
        out = nodes[spec.nindex_out[0]]
        x32 = out.astype(jnp.float32)
        ent: Dict[str, jax.Array] = {"absmax": jnp.max(jnp.abs(x32))}
        if spec.type in self._HEALTH_DEAD_TYPES:
            ent["zero_frac"] = jnp.mean((x32 == 0.0).astype(jnp.float32))
        if train and spec.type in ("batch_norm", "batch_norm_no_ma"):
            xin = nodes[spec.nindex_in[0]].astype(jnp.float32)
            axes = tuple(range(xin.ndim - 1))
            var = jnp.maximum(
                jnp.mean(jnp.square(xin), axes)
                - jnp.square(jnp.mean(xin, axes)), 0.0)
            ent["bn_var_min"] = jnp.min(var)
        sink[spec.name] = ent

    # -- pipeline staging (config-driven pp, parallel/pipeline.py) ---------
    def stage_partition(self, n_stages: int) -> List[Tuple[int, int]]:
        """Partition layers into ``n_stages`` contiguous [lo, hi) ranges
        from per-layer ``stage = k`` config annotations (a layer without
        one inherits the previous layer's stage). Loss layers are excluded
        from the pipeline body — they run on the reassembled full batch.
        Validates: stages non-decreasing and covering 0..S-1, no reads
        from later stages, and no stateful layers in the body beyond
        batch_norm/moe (whose moments/aux-loss ride the schedule's
        sinks). Cross-stage skips and heterogeneous boundary shapes are
        fine: each boundary's carried node set (``self._stage_carried``)
        flat-packs into one ring register (trainer pack/unpack)."""
        g = self.graph
        n_body = len(g.layers)
        while n_body and self.layers[n_body - 1].is_loss:
            n_body -= 1
        for li in range(n_body):
            if self.layers[li].is_loss:
                raise ValueError(
                    "pipeline_parallel: loss layers must come last")
        stages = []
        cur = 0
        for li in range(n_body):
            for k, v in g.layers[li].cfg:
                if k == "stage":
                    nxt = int(v)
                    if nxt < cur or nxt > cur + 1:
                        raise ValueError(
                            f"pipeline stage ids must be contiguous and "
                            f"non-decreasing; layer {g.layers[li].name!r} "
                            f"jumps {cur} -> {nxt}")
                    cur = nxt
            stages.append(cur)
        if cur != n_stages - 1:
            raise ValueError(
                f"config declares stages 0..{cur} but pipeline_parallel = "
                f"{n_stages}")
        ranges: List[Tuple[int, int]] = []
        lo = 0
        for s in range(n_stages):
            hi = lo
            while hi < n_body and stages[hi] == s:
                hi += 1
            if hi == lo:
                raise ValueError(f"pipeline stage {s} has no layers")
            ranges.append((lo, hi))
            lo = hi
        # validations over the partition
        node_stage = {0: 0}
        for i in range(g.extra_data_num):
            node_stage[1 + i] = 0
        last_consumer: Dict[int, int] = {}
        for s, (lo, hi) in enumerate(ranges):
            for li in range(lo, hi):
                layer, spec = self.layers[li], g.layers[li]
                if ((layer.has_state or layer.init_state(
                        self._in_shapes_of[li]))
                        and not getattr(layer, "pp_batch_stats", False)
                        and not getattr(layer, "pp_aux_loss", False)
                        and not getattr(layer, "pp_state_tick", False)):
                    # batch_norm is admitted: its microbatch moments ride
                    # the schedule's stat sink and merge after the ring.
                    # moe is admitted: its _aux_loss rides the schedule's
                    # per-stage scalar accumulator (differentiated).
                    # insanity is admitted: its annealing counter is read
                    # frozen by the microbatches and ticked once per step
                    # by the trainer (pp_state_tick). Remaining stateful
                    # layers (pairtest's divergence log) cannot pipeline.
                    raise ValueError(
                        f"pipeline_parallel: stateful layer "
                        f"{spec.name!r} ({spec.type}) is not supported in "
                        f"the pipeline body")
                for ni in spec.nindex_in:
                    src = node_stage.get(ni)
                    if src is None:
                        raise ValueError(
                            f"layer {spec.name!r}: input node produced in "
                            "a later stage")
                    # cross-stage reads are fine: every node produced in
                    # stages <= i and consumed after i rides the flat ring
                    # register (see stage_carried / _pp_pipeline_fn pack)
                    last_consumer[ni] = max(last_consumer.get(ni, -1), s)
                for ni in spec.nindex_out:
                    # FIRST production stage: an in-place (layer[+0])
                    # rewrite in a later stage must not hide the node from
                    # earlier boundaries — the pre-rewrite value still has
                    # to ride the register to reach that stage (pack reads
                    # the stage-local node map, so each boundary carries
                    # the latest value at its cut)
                    node_stage.setdefault(ni, s)
        # the loss tail runs on the reassembled batch, seeded with the top
        # body node PLUS any other body node a tail layer reads (auxiliary
        # loss heads, GoogLeNet-style): each extra seed rides the carried
        # register to the last stage like any cross-stage skip
        top_node = g.layers[n_body - 1].nindex_out[0]
        tail_avail = {top_node}
        tail_reads = set()
        for li in range(n_body, len(g.layers)):
            spec = g.layers[li]
            for ni in spec.nindex_in:
                if ni not in tail_avail:
                    if ni not in node_stage:
                        raise ValueError(
                            f"pipeline_parallel: loss-tail layer "
                            f"{spec.name!r} reads node "
                            f"{g.node_names[ni]!r}, which no pipeline "
                            "body stage produces")
                    tail_reads.add(ni)
                    tail_avail.add(ni)
            tail_avail.update(spec.nindex_out)
        self._tail_seeds = sorted({top_node} | tail_reads)
        # carried set per boundary i: nodes produced in stages <= i still
        # needed after i — every tail seed (the final body node, plus aux
        # loss-head inputs) is "consumed" by the loss tail, so it is
        # carried to the end. Boundary shapes/counts may differ per cut:
        # the trainer packs each boundary's carried nodes into one flat
        # max-size ring register (_pp_pipeline_fn pack).
        for ni in self._tail_seeds:
            last_consumer[ni] = len(ranges)
        self._stage_carried = [
            sorted(ni for ni, s_prod in node_stage.items()
                   if s_prod <= i and last_consumer.get(ni, -1) > i)
            for i in range(len(ranges) - 1)]
        for i, carried in enumerate(self._stage_carried):
            if not carried:
                raise ValueError(
                    f"pipeline boundary {i} carries no nodes — stage "
                    f"{i + 1} reads nothing from earlier stages")
        return ranges

    def tp_manual_plan(self, tp_size: int, stage_ranges=None,
                       train: bool = True) -> Dict[int, Dict[str, Any]]:
        """Static plan for MANUAL tensor parallelism inside pipeline stages.
        The pp step cannot leave the model axis to GSPMD — automatic
        partitioning inserts model-axis collectives *inside* lax.switch
        branches with module-wide rendezvous, which deadlocks (devices in
        different stages never reach each other's ops). The manual scheme
        slices each planned weight along its 'model' dim (zero-padded to a
        tp multiple when the dim does not divide) and computes with the
        local shard; the output all-gather is DEFERRED through chains of
        channel-wise followers (``Layer.tp_follow`` — BN, activations,
        pooling, bias/prelu, whose per-channel params/state slice along)
        and lands only where a channel-mixing consumer needs the full
        activation, or at the stage boundary. Every collective stays
        scoped to the model peers of one stage, which all execute the
        same branch — the generalization of the reference's fullc_gather
        hybrid (async_updater-inl.hpp:68-94).

        Returns {layer_index: entry} with optional entry keys:
          ``params``  {key: (dim, orig)} — pad dim to a tp multiple of
                      orig, then slice this shard's span;
          ``state``   {key: orig} — dim-0 channel slices (BN running
                      stats at eval);
          ``gather``  {input_pos: orig} — all-gather(+trim) these inputs
                      before apply (first channel-mixing consumer);
          ``out_sharded`` orig — outputs stay channel-sharded;
          ``sink_gather`` orig — all-gather this layer's stat-sink
                      moments back to full width after apply.
        ``stage_ranges`` must be the pipeline's body partition — sharded
        values never cross a stage boundary (apply_stage gathers wanted
        nodes at stage end), so the walk resets per stage."""
        plan: Dict[int, Dict[str, Any]] = {}
        if tp_size <= 1:
            return plan
        g = self.graph
        ranges = stage_ranges or [(0, len(g.layers))]
        excluded: List[Tuple[str, str]] = []
        followed: List[str] = []

        def slice_dims(li, layer):
            """{key: (dim, orig)} for a producer slice, or a reason str.
            Specs come from the RULE TABLE (param_pspecs), not the
            layer declaration directly — a config ``partition_rules``
            override changes the manual plan the same way it changes
            GSPMD placement, keeping the manual execution plan derived
            from the one declarative source."""
            if getattr(layer, "tp_manual_axis", None) is None:
                return "no tp_manual_axis"
            pspecs = self.param_pspecs().get(layer.name) or {}
            shapes = self.param_shapes().get(layer.name, {})
            # rules cover only params the layer actually created
            # (no_bias conv has no "bias" leaf to match)
            dims = {key: d for key, ps in pspecs.items() if key in shapes
                    for d, ax in enumerate(ps)
                    if ax == "model"
                    or (isinstance(ax, tuple) and "model" in ax)}
            if not dims:
                return "no 'model' dim in the partition rules"
            sizes = {shapes[key].shape[d] for key, d in dims.items()}
            if len(sizes) != 1:
                return "mixed 'model' dims"
            orig = sizes.pop()
            if orig < tp_size:
                return f"'model' dim {orig} < tp {tp_size}"
            return {key: (d, orig) for key, d in dims.items()}

        for lo, hi in ranges:
            sharded: Dict[int, int] = {}   # node -> orig trailing width
            for li in range(lo, hi):
                spec, layer = g.layers[li], self.layers[li]
                ent: Dict[str, Any] = {}
                in_sh = {pos: sharded[ni]
                         for pos, ni in enumerate(spec.nindex_in)
                         if ni in sharded}
                if in_sh:
                    can_follow = (len(spec.nindex_in) == 1
                                  and len(spec.nindex_out) == 1
                                  and not spec.is_shared
                                  and layer.tp_followable(train))
                    if can_follow:
                        orig = in_sh[0]
                        if layer.tp_channel_params:
                            ent["params"] = {k: (0, orig)
                                             for k in layer.tp_channel_params}
                        if layer.tp_channel_state and layer.has_state:
                            ent["state"] = {k: orig
                                            for k in layer.tp_channel_state}
                        if getattr(layer, "pp_batch_stats", False):
                            ent["sink_gather"] = orig
                        ent["out_sharded"] = orig
                        sharded[spec.nindex_out[0]] = orig
                        followed.append(layer.name)
                        plan[li] = ent
                        continue
                    ent["gather"] = dict(in_sh)
                    for pos in in_sh:
                        sharded.pop(spec.nindex_in[pos], None)
                if not spec.is_shared and layer.has_params:
                    dims = slice_dims(li, layer)
                    if isinstance(dims, str):
                        excluded.append((layer.name, dims))
                    else:
                        orig = next(iter(dims.values()))[1]
                        ent["params"] = dims
                        ent["out_sharded"] = orig
                        sharded[spec.nindex_out[0]] = orig
                if ent:
                    plan[li] = ent
        # layers outside the plan compute replicated — say so once, loudly
        # enough to explain a flat memory/throughput curve, quiet enough
        # not to spam (grouped by reason, a few example names each)
        if not self._tp_plan_logged:
            self._tp_plan_logged = True
            by_reason: Dict[str, List[str]] = {}
            for n, why in excluded:
                by_reason.setdefault(why, []).append(n)
            detail = "; ".join(
                f"{why}: {len(names)} ({', '.join(names[:4])}"
                + (", ..." if len(names) > 4 else "") + ")"
                for why, names in by_reason.items())
            print(f"tp_manual_plan: {len(excluded)}/{len(self.layers)} "
                  f"layer(s) compute replicated across the model axis "
                  f"(tp={tp_size}); {len(followed)} follow channel-sharded"
                  + (f" — {detail}" if detail else ""))
        return plan

    def apply_stage(self, lo: int, hi: int, params: Params, seed,
                    rng: jax.Array, train: bool,
                    state: Optional[NetState] = None,
                    tp_axis: Optional[str] = None,
                    tp_size: int = 1,
                    tp_plan: Optional[Dict[int, Dict[str, Any]]] = None,
                    want: Optional[List[int]] = None,
                    seq_axis: Optional[str] = None,
                    data_axis: Optional[str] = None):
        """Run layers [lo, hi) on one microbatch. ``seed`` is the raw data
        array (lo == 0) or a {node_index: value} dict of carried nodes
        (stage_carried). Returns ``(out, stats)`` where ``out`` is the
        range's final node value, or {node_index: value} for the nodes in
        ``want`` when given (the carried set of the next boundary —
        cross-stage skips ride along). ``stats``: raw microbatch moments
        of any batch-stat layers (batch_norm) in the range — train only;
        the pipeline schedule accumulates these and the trainer applies
        one exact full-batch running-stat update after the ring.
        ``state`` is read-only (eval-time BN running stats)."""
        g = self.graph
        nodes: Dict[int, jax.Array] = {}
        if isinstance(seed, dict):
            nodes.update(seed)
        else:
            nodes[0] = seed
        sink: Dict[str, Any] = {}
        tp_plan = tp_plan or {}
        sharded: Dict[int, int] = {}   # node -> orig trailing width

        def slice_leaf(leaf, d, orig, me):
            """This shard's span of ``leaf`` along dim ``d``: zero-pad a
            non-divisible dim to a tp multiple first — pad rows/channels
            compute zeros that the eventual gather trims, and the
            pad+dynamic_slice pair transposes to exact zero-padded-slice
            gradients under autodiff."""
            span = -(-orig // tp_size)
            if span * tp_size != orig:
                pw = [(0, 0)] * leaf.ndim
                pw[d] = (0, span * tp_size - orig)
                leaf = jnp.pad(leaf, pw)
            return jax.lax.dynamic_slice_in_dim(leaf, me * span, span,
                                                axis=d)

        def gather_trim(v, orig):
            """Deferred manual-tp all-gather on the trailing channel axis,
            trimmed back to the original width (padding case) — a
            model-group-scoped collective every model peer of this stage
            executes (see tp_manual_plan)."""
            full = jax.lax.all_gather(v, tp_axis, axis=v.ndim - 1,
                                      tiled=True)
            if full.shape[-1] != orig:
                full = jax.lax.slice_in_dim(full, 0, orig, axis=-1)
            return full

        for li in range(lo, hi):
            spec, layer = g.layers[li], self.layers[li]
            # seq/data axes bound under the sequence-parallel pipeline:
            # mha takes the ring path, moe routes globally — collectives
            # scoped to this stage's seq/data peers, which all execute
            # the same switch branch
            ctx = ApplyCtx(train=train, rng=jax.random.fold_in(rng, li),
                           compute_dtype=self.compute_dtype,
                           stat_sink=sink if train else None,
                           seq_axis=seq_axis, data_axis=data_axis,
                           seq_gather_kv=seq_axis is not None)
            ent = tp_plan.get(li)
            if ent:
                # first channel-mixing consumer of a sharded chain:
                # materialize the full activation here
                for pos, orig in ent.get("gather", {}).items():
                    ni = spec.nindex_in[pos]
                    if ni in sharded:
                        nodes[ni] = gather_trim(nodes[ni], sharded.pop(ni))
            inputs = [nodes[ni] for ni in spec.nindex_in]
            lstate = (state or {}).get(layer.name, {})
            lparams = params.get(layer.name, {})
            if ent and ("params" in ent or "state" in ent):
                me = jax.lax.axis_index(tp_axis)
                if "params" in ent:
                    lparams = dict(lparams)
                    for key, (d, orig) in ent["params"].items():
                        lparams[key] = slice_leaf(lparams[key], d, orig, me)
                if "state" in ent and lstate:
                    lstate = dict(lstate)
                    for key, orig in ent["state"].items():
                        lstate[key] = slice_leaf(lstate[key], 0, orig, me)
            with layer_scope(spec.name):
                outputs, _ = layer.apply(lparams, lstate, inputs, ctx)
            if ent and "sink_gather" in ent and layer.name in sink:
                # batch-stat followers (BN) computed channel-local moments;
                # gather them back to full width so the trainer's post-ring
                # merge and the stats_sd probe see the unsharded shape
                sink[layer.name] = jax.tree_util.tree_map(
                    lambda v: gather_trim(v, ent["sink_gather"]),
                    sink[layer.name])
            if ent and "out_sharded" in ent:
                sharded[spec.nindex_out[0]] = ent["out_sharded"]
            for ni, out in zip(spec.nindex_out, outputs):
                nodes[ni] = out
        # stage end: every value leaving the stage (ring register, capture
        # banks, tail seeds) gathers to full width — sharded values never
        # cross stage boundaries (tp_manual_plan resets its walk per stage)
        if want is not None:
            return {ni: (gather_trim(nodes[ni], sharded[ni])
                         if ni in sharded else nodes[ni])
                    for ni in want}, sink
        ni = g.layers[hi - 1].nindex_out[0]
        out = nodes[ni]
        if ni in sharded:
            out = gather_trim(out, sharded[ni])
        return out, sink

    def apply_tail(self, body_hi: int, params: Params, state: NetState,
                   seeds: Dict[int, jax.Array],
                   label: Optional[jax.Array],
                   mask: jax.Array, rng: jax.Array,
                   train: bool,
                   label_slices: Optional[Dict[Tuple[int, int],
                                               jax.Array]] = None,
                   seq_axis: Optional[str] = None,
                   data_axis: Optional[str] = None,
                   want: Optional[List[int]] = None) -> ForwardResult:
        """Run the loss layers [body_hi, end) on the pipeline's output
        (they are row-wise, so GSPMD batch sharding applies). ``seeds``
        is a {node_index: value} dict: the top body node plus any other
        body node a tail layer reads (auxiliary loss heads —
        ``_tail_seeds``). ``want``: node indices whose POST-tail values
        the caller captures (metric bindings / extraction on nodes the
        tail writes) — returned in ``result.nodes`` keyed by index.
        ``label_slices``/``seq_axis``/``data_axis`` mirror ``apply`` for
        the sequence-parallel pipeline: pre-sliced width-sharded labels,
        and manual axes bound in the loss layers' ctx."""
        g = self.graph
        nodes: Dict[int, jax.Array] = dict(seeds)
        new_state: NetState = dict(state)
        total_loss = jnp.zeros((), jnp.float32)
        for li in range(body_hi, len(g.layers)):
            spec, layer = g.layers[li], self.layers[li]
            ctx = ApplyCtx(train=train, rng=jax.random.fold_in(rng, li),
                           compute_dtype=self.compute_dtype,
                           seq_axis=seq_axis, data_axis=data_axis)
            inputs = [nodes[ni] for ni in spec.nindex_in]
            with layer_scope(spec.name):
                outputs, lstate_out = layer.apply(
                    params.get(layer.name, {}),
                    new_state.get(layer.name, {}), inputs, ctx)
            if lstate_out:
                new_state[layer.name] = lstate_out
            for ni, out in zip(spec.nindex_out, outputs):
                nodes[ni] = out
            if layer.is_loss and (label is not None
                                  or label_slices is not None):
                a, b = g.label_slice(layer.target)
                with layer_scope(spec.name):
                    lab = (label_slices[(a, b)]
                           if label_slices is not None else label[:, a:b])
                    total_loss = total_loss + layer.loss(
                        outputs, lab.astype(jnp.float32), mask)
        out = nodes[g.layers[-1].nindex_out[0]]
        return ForwardResult(loss=total_loss, state=new_state,
                             nodes={ni: nodes[ni] for ni in want}
                             if want else None,
                             out=out)

    def node_value(self, result: ForwardResult, name: str) -> jax.Array:
        """Look up a captured node by name or 'top[-k]' style index."""
        assert result.nodes is not None, "apply(capture_nodes=True) required"
        return result.nodes[name]

    def param_shapes(self) -> Dict[str, Any]:
        """ShapeDtypeStruct tree of init()'s params (eval_shape — no
        values materialize), cached. The rule matcher and the FSDP
        planner key off this."""
        if self._param_shapes_cache is None:
            self._param_shapes_cache = jax.eval_shape(
                lambda: self.init(jax.random.PRNGKey(0))[0])
        return self._param_shapes_cache

    def partition_rules(self):
        """The per-model partition-rule table (parallel/rules.py):
        custom ``partition_rules`` config entries first (override
        wins), then ONE anchored rule per parameter leaf — spec from
        the layer type's declaration (``layer.param_pspecs``), P()
        (replicated) for everything else. ``(^|/)`` anchoring lets the
        same table cover optimizer state, whose momentum/moment trees
        mirror the params under "mom"/"m1"/"m2" prefixes — so params
        AND optimizer state shard from one declarative source."""
        import re as _re

        from jax.sharding import PartitionSpec as P

        from .parallel.rules import parse_rule_string, tree_paths
        rules = (parse_rule_string(self.sharding_cfg.partition_rules)
                 if self.sharding_cfg.partition_rules else [])
        # optimizer-state mirrors are the ONLY non-layer prefixes the
        # generated anchors admit — a bare (^|/) would let one layer's
        # rule capture a suffix of another layer's NESTED leaf (layer
        # 'o' vs 'attn1/o/wmat')
        opt = r"^(?:(?:mom|m1|m2)/)?"
        for spec, layer in zip(self.graph.layers, self.layers):
            if spec.is_shared or not layer.has_params:
                continue
            declared = dict(tree_paths(
                layer.param_pspecs() or {},
                is_leaf=lambda v: isinstance(v, tuple))[0])
            shapes = self.param_shapes().get(layer.name, {})
            for path, _leaf in tree_paths(shapes)[0]:
                ps = declared.get(path)
                rules.append((
                    opt + rf"{_re.escape(layer.name)}/{_re.escape(path)}$",
                    P(*ps) if ps is not None else P()))
        return rules

    def param_pspecs(self) -> Dict[str, Any]:
        """PartitionSpec tree matching init()'s params, derived from
        the partition-rule table (size-1 axes = replicated, so this is
        always safe to apply). The manual-tp plan and the trainer's
        placement both read THIS — one source of truth; the per-layer
        ``layer.param_pspecs`` declarations only feed the rule table
        (asserted equal in tests/test_partition_rules.py)."""
        if self._rule_pspecs_cache is None:
            from .parallel.rules import match_partition_rules
            self._rule_pspecs_cache = match_partition_rules(
                self.partition_rules(), self.param_shapes())
        return self._rule_pspecs_cache

    # -- introspection -----------------------------------------------------
    def param_tag(self, layer_name: str, param_name: str) -> str:
        """Tag used for lr/wd scoping: 'wmat' or 'bias'."""
        from .optim import tag_for_param
        return tag_for_param(param_name)

    def out_shape(self) -> Shape3:
        return self.node_shapes[self.graph.layers[-1].nindex_out[0]]

    def input_nhwc(self, batch: int) -> Tuple[int, int, int, int]:
        return to_nhwc(self.graph.input_shape, batch)
