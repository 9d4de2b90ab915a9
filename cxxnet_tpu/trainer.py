"""Trainer: the INetTrainer-equivalent orchestrator.

Reference: INetTrainer (nnet.h:18-92) implemented by CXXNetThreadTrainer
(nnet_impl-inl.hpp:22-488), which splits batches over per-GPU worker threads
and syncs gradients through mshadow-ps. Here a single jitted train step over a
device mesh replaces the whole thread/PS machinery: the batch is sharded over
the mesh's 'data' axis, params are replicated, and XLA inserts the gradient
all-reduce over ICI (the reference's per-layer Push/PullReq with priorities
becomes XLA's latency-hiding schedule). ``update_period`` gradient
accumulation (nnet_impl-inl.hpp:166-167) is implemented with a grad
accumulator pytree and a trace-time branch. Because batch stats reduce over
the sharded batch axis inside jit, batch_norm is effectively synchronized
across devices (sync-BN) — a deliberate improvement over the reference's
per-GPU stats (SURVEY §7 risks).

API surface mirrors the reference trainer: init_model, save/load_model,
start_round, update, evaluate, predict, extract_feature, copy_model_from,
set/get_weight.
"""

from __future__ import annotations

import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ConfigPairs
from .graph import build_graph, global_param
from .metrics import MetricSet
from .model import Network
from .optim import create_optimizer
from .parallel import MeshContext, make_mesh_context
from .io.data import DataBatch
from .resilience import failpoints
from .telemetry import modelhealth
from .telemetry.trace import TRACER
from . import checkpoint as ckpt

_METRIC_RE = re.compile(r"^metric(?:\[([^,\]]+)(?:,([^\]]+))?\])?$")
_TOP = "!top"
#: prefix of a key of the step's node outputs that holds, for the node
#: key after it, the loss head's own device-side reduction for the train
#: metric (``LMLossLayer.metric_stats``: three numbers a row) instead of
#: the node
_REDUCED = "!reduced:"
#: key of the step's node outputs under which the sigmoid-routed moe
#: layers' ``stats`` vectors ride, by layer name: fetched with the train
#: metric, one step late, at no sync of their own
_MOE = "!moe"
_DSA = "!dsa"


def _collect_nodes(res, needed, reduce=None, drop_top=False):
    """Assemble the step's node outputs: the top node plus any captured
    metric/extract-bound nodes — shared by every train/eval step builder.
    ``reduce`` ({node key: node name}, the std train step's): those keys
    carry the loss head's reduction of the node under ``_REDUCED + key``
    in the node's place; ``drop_top``: no metric reads the top node."""
    nodes = {} if drop_top else {_TOP: res.out}
    if needed:
        nodes.update({n: res.nodes[n] for n in needed})
    for key, name in (reduce or {}).items():
        if key in nodes and name in (res.metric_stats or {}):
            del nodes[key]
            nodes[_REDUCED + key] = res.metric_stats[name]
    return nodes


def _layer_stats(net_state, key="stats"):
    """{layer: its state's vector under ``key``}: ``stats`` of the
    no-drop moe layers, ``dsa_stats`` of the sparse attention layers."""
    return {name: st[key] for name, st in net_state.items()
            if isinstance(st, dict) and key in st}


def _fold_input(data, net):
    """input_fold entry point inside the compiled step: a
    ``(uint8-batch, mean, factor)`` tuple is normalized in-trace
    (ops/stem.decode_normalize) into the compute dtype; a plain array
    passes through untouched. The tuple's mean/factor are traced ARGUMENTS,
    not baked constants, so two iterators with different normalization
    metadata share one compiled step."""
    if not isinstance(data, tuple):
        return data
    x, mean, factor = data
    from .ops.stem import decode_normalize
    with jax.named_scope("input_fold"):
        return decode_normalize(x, mean, factor, net.compute_dtype)


def _chain_scan(one, length):
    """Wrap a modal one-step body into a ``length``-step lax.scan chain
    (update_chain): the (params, opt_state, net_state, rng) carry threads
    through; accum is stubbed (no update_period in chains), per-step node
    captures are discarded (DCE'd), and the per-step losses stack.
    ``one``: (params, opt_state, net_state, accum, data, label, mask,
    rng, sched) -> (params, opt_state, net_state, accum, loss, nodes,
    rng) — the shared signature of the std/sp/pp one-step bodies."""
    def step(params, opt_state, net_state, data, label, mask, rng, sched):
        def sbody(carry, _):
            p, o, s, r = carry
            p, o, s, _a, loss, _n, r = one(
                p, o, s, {}, data, label, mask, r, sched)
            return (p, o, s, r), loss
        (params, opt_state, net_state, rng), losses = jax.lax.scan(
            sbody, (params, opt_state, net_state, rng), None,
            length=length)
        return params, opt_state, net_state, losses, rng
    return step


def _scaled_value_and_grad(loss_fn, params, opt_state):
    """value_and_grad with fp16 dynamic loss scaling. When the optimizer
    state carries a ``"_mp"`` scaler (compute_dtype = float16), the
    differentiated loss is multiplied by the current scale — so small
    fp16 gradients clear the subnormal floor — and the RETURNED loss is
    divided back (the scale is a power of two, so the division is exact).
    Gradients stay scaled here; Optimizer.update unscales them and
    handles the overflow skip/halve. bf16/fp32 policies have no "_mp"
    entry and take the plain path, identical bit-for-bit to before."""
    mp = opt_state.get("_mp") if isinstance(opt_state, dict) else None
    if mp is None:
        return jax.value_and_grad(loss_fn, has_aux=True)(params)
    scale = mp["scale"]

    def scaled(p):
        loss, aux = loss_fn(p)
        return loss * scale, aux
    (loss, aux), grads = jax.value_and_grad(scaled, has_aux=True)(params)
    return (loss / scale, aux), grads


def _optimizer_scope():
    """The ``optimizer`` scope every step builder (std, sp, pp) traces
    its accumulation and parameter update under — the third phase of
    telemetry/traceparse.classify, beside the ``jvp``/``transpose``
    wrappers autodiff puts on the forward and the backward itself."""
    return jax.named_scope("optimizer")


def _apply_accum(opt, period, params, opt_state, accum, sched,
                 finite_axes=()):
    """The period-boundary apply: scale the accumulated grads, step the
    optimizer, zero the accumulator. ONE definition shared by the
    static (update) and traced (accumulating-chain lax.cond) callers so
    the two paths cannot silently diverge. The accumulator is fp32 (it
    starts as zeros_like the fp32 masters and jnp.add promotes), so
    update_period composes with every compute-dtype policy; under fp16
    it holds loss-SCALED sums that Optimizer.update unscales at apply."""
    with _optimizer_scope():
        scaled = jax.tree_util.tree_map(lambda g: g / period, accum)
        params, opt_state = opt.update(params, scaled, opt_state, sched,
                                       finite_axes=finite_axes)
        return params, opt_state, jax.tree_util.tree_map(
            jnp.zeros_like, accum)


def _apply_grads(opt, period, do_update, params, opt_state, accum, grads,
                 sched, finite_axes=()):
    """Gradient accumulation (update_period) + optimizer step — shared by
    the GSPMD and shard_map train-step builders. ``finite_axes``: manual
    mesh axes over which gradient LEAVES are sharded (pp's FSDP 'pipe'
    axis) — threaded to the fp16 overflow check so every shard agrees on
    skip-vs-apply (see Optimizer.update)."""
    if period > 1:
        with _optimizer_scope():
            accum = jax.tree_util.tree_map(jnp.add, accum, grads)
        if do_update:
            params, opt_state, accum = _apply_accum(
                opt, period, params, opt_state, accum, sched,
                finite_axes=finite_axes)
    else:
        with _optimizer_scope():
            params, opt_state = opt.update(params, grads, opt_state,
                                           sched, finite_axes=finite_axes)
    return params, opt_state, accum


class Trainer:
    def __init__(self, cfg: ConfigPairs, mesh_ctx: Optional[MeshContext] = None):
        self.cfg = list(cfg)
        self.graph = build_graph(cfg)
        self.net = Network(self.graph, cfg)
        # mixed-precision policy (config.Policy): fp32 masters, layers
        # compute in policy.compute_dtype, loss/metrics/outputs fp32
        self.policy = self.net.policy
        gp = lambda n, d: global_param(cfg, n, d)
        self.batch_size = int(gp("batch_size", "128"))
        self.update_period = int(gp("update_period", "1"))
        self.eval_train = int(gp("eval_train", "1"))
        self.seed = int(gp("seed", "0"))
        self.silent = int(gp("silent", "0"))
        # save_async = 1: checkpoint IO — including the device->host
        # staging transfer — happens on a background thread; the
        # critical path pays one async device-copy dispatch and
        # training resumes while the previous checkpoint is written
        self.save_async = int(gp("save_async", "0"))
        self._save_thread = None
        # sharded checkpointing (doc/tasks.md "Sharded checkpointing"):
        # rounds write as r%04d/ shard SETS instead of one blob; layout
        # derives from the partition rules, resume quorum-validates
        from .config import parse_ckpt_config
        _ckpt_cfg = parse_ckpt_config(cfg)
        self.shard_ckpt = _ckpt_cfg.shard_ckpt
        self.shard_ckpt_shards = _ckpt_cfg.shard_ckpt_shards
        self._warned_no_ckpt_barrier = False
        # model-health probe (doc/tasks.md "Model health"): health = 1
        # makes the std/sp step bodies compute compact per-layer
        # numerics IN-TRACE and return them as one extra fp32 pytree;
        # health = 0 leaves every step builder on the exact pre-health
        # path (jaxpr-identity pinned by tests/test_modelhealth.py)
        from .config import parse_health_config
        self.health_cfg = parse_health_config(cfg)
        self.health_on = bool(self.health_cfg.enabled)
        self._last_health = None
        self._health_batch = None
        self._warned_health_chain = False
        dev = gp("dev", "")
        model_parallel = int(gp("model_parallel", "1"))
        seq_parallel = int(gp("seq_parallel", "1"))
        pipeline_parallel = int(gp("pipeline_parallel", "1"))
        self.mesh = mesh_ctx or make_mesh_context(
            dev or "tpu", model_parallel=model_parallel,
            seq_parallel=seq_parallel,
            pipeline_parallel=pipeline_parallel)
        self._sp = self.mesh.seq_parallel
        self._pp = self.mesh.pipeline_parallel
        # microbatch count for the GPipe schedule (reference has no analog;
        # update_period is the closest — but that serializes, this overlaps)
        self._pp_microbatch = int(gp("pipeline_microbatch",
                                     str(max(self._pp, 1))))
        self.optimizer = create_optimizer(self.graph.updater_type, cfg)
        # rule-driven sharding namespace (validated in Network.__init__)
        self.sharding_cfg = self.net.sharding_cfg
        self._fsdp_axis = self.sharding_cfg.fsdp_axis
        if self._fsdp_axis and (self._sp > 1 or self._pp > 1):
            raise ValueError(
                "fsdp_axis composes with the std (GSPMD dp/tp) step "
                "only; the pp step has its own at-rest FSDP over "
                "'pipe' and sp keeps params replicated")
        self._selection_reported = False
        if self.health_on and self._pp > 1:
            # the pp step's stat plumbing is the microbatch ring's stat
            # sink — per-step health trees do not ride it; std (GSPMD
            # dp/tp) and sp steps carry the probe, pp falls back loudly
            print("WARNING: health=1 has no in-step probe on "
                  "pipeline-parallel meshes; model-health telemetry "
                  "disabled for this run (std/sp steps only)",
                  flush=True)
            self.health_on = False
        # metric bindings (reference nnet_impl-inl.hpp:73-83)
        self.metric = MetricSet()
        self.train_metric = MetricSet()
        self._metric_nodes: List[Optional[str]] = []
        for name, val in cfg:
            m = _METRIC_RE.match(name)
            if not m:
                continue
            label_field, node = m.group(1), m.group(2)
            if label_field is None or node is None:
                self.metric.add(val, "label", None)
                self.train_metric.add(val, "label", None)
                self._metric_nodes.append(None)
            else:
                self.metric.add(val, label_field, node)
                self.train_metric.add(val, label_field, node)
                self._metric_nodes.append(node)
        # a sequence loss head reduces its node on the device to what
        # the train metric needs (three numbers a row) where every
        # metric bound to that node can take them; a top node that no
        # metric reads, while others are read, is not fetched at all
        top_name = (self.graph.node_names[
            self.graph.layers[-1].nindex_out[0]]
            if self.graph.layers else None)
        heads = {self.graph.node_names[spec.nindex_out[0]]
                 for spec, layer in zip(self.graph.layers,
                                        self.net.layers)
                 if hasattr(layer, "metric_stats")}
        self._reduce_keys: Dict[str, str] = {}
        for key in {n or _TOP for n in self._metric_nodes}:
            name = top_name if key == _TOP else key
            if name in heads and all(
                    m.takes_reduced for m, n in zip(
                        self.train_metric.metrics, self._metric_nodes)
                    if (n or _TOP) == key):
                self._reduce_keys[key] = name
        self._drop_top = bool(self._metric_nodes
                              and None not in self._metric_nodes
                              and top_name in heads)
        # counters (reference epoch_counter = #updates; round = epoch)
        self.epoch_counter = 0
        self.sample_counter = 0
        self.round_counter = 0
        self.params = None
        self.net_state = None
        self.opt_state = None
        self.accum = None
        self._base_key = jax.random.PRNGKey(self.seed)
        self._step_count = 0
        self._train_step_fns: Dict[bool, Any] = {}
        # the step update() last ran, with its arguments' shapes: what
        # telemetry.profiler.step_hlo_text() lowers on demand
        self._described_step = None
        self._described_args = None
        self.last_drain_s = 0.0     # see _drain_pending_metric
        self._eval_step_fn = None
        self._last_loss = None
        self._sched_cache = None
        self._sched_stack_cache = None
        self._cnt_cache = None
        self._mask_cache = None
        self._sp_label_cache = None
        self._rng_key = None
        self._norm_fn = None
        self._fold_cache = None
        # input_fold (doc/tasks.md "Input fold"): device_normalize
        # batches enter the compiled train step as uint8 and the
        # cast/mean/scale happens IN-TRACE (ops/stem.py), killing the
        # separate normalize dispatch's fp32 HBM round-trip of the whole
        # batch (~310 MB/step at flagship shape). Exact math (f32
        # compute, one cast to the compute dtype — where the layers'
        # own astype puts the input anyway), so auto means ON; off is
        # the escape hatch. std (GSPMD dp/tp) train path only: the
        # sp/pp shard_map steps keep the eager normalize.
        from .config import parse_auto_on_off
        self.input_fold = (
            parse_auto_on_off("input_fold",
                              gp("input_fold", "auto")) != "off"
            and self._sp == 1 and self._pp == 1)
        # one-step deferred train-metric fetch: device->host reads of step
        # N's outputs happen after step N+1 is dispatched, so the transfer
        # overlaps compute instead of syncing every update (the reference
        # accumulates metrics only after WaitAllJobs; XLA async dispatch
        # makes the lagged fetch free)
        self._pending_metric = None
        self._params_finite_fn = None
        if self.batch_size % self.mesh.data_parallel:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by data-parallel "
                f"degree {self.mesh.data_parallel}")
        if self._sp > 1:
            self._check_seq_parallel_ok()
        self._pp_ranges = None
        if self._pp > 1:
            if self._sp > 1:
                # pp x sp: stages run ring attention / global MoE routing
                # over the 'seq' axis INSIDE the pipe schedule — legal for
                # the same reason manual tp is (a device's seq peers share
                # its pipe coordinate, so every seq collective is executed
                # by peers taking the same switch branch). Sequence nets
                # only, like plain sp.
                self._check_seq_parallel_ok()
            # model_parallel composes via MANUAL tensor parallelism:
            # apply_stage slices fullc/conv weights per model shard and
            # all-gathers outputs (Network.tp_manual_plan). GSPMD-auto
            # model sharding is NOT an option here — it inserts
            # module-wide collectives inside the lax.switch stage
            # branches, which deadlocks (see tp_manual_plan's docstring)
            if self.graph.extra_data_num:
                raise ValueError("pipeline_parallel does not support "
                                 "extra_data")
            if self.batch_size % (self.mesh.data_parallel
                                  * self._pp_microbatch):
                raise ValueError(
                    f"batch_size {self.batch_size} not divisible by "
                    f"data_parallel x pipeline_microbatch = "
                    f"{self.mesh.data_parallel}x{self._pp_microbatch}")
            # validates staging and fails fast on unpipelinable graphs
            self._pp_ranges = self.net.stage_partition(self._pp)
            # non-top metric/extract nodes must be BODY nodes — their
            # per-microbatch values are banked through the schedule's
            # stat sink and reassembled (nodes inside the loss tail other
            # than the top have no bank)
            n_body = self._pp_ranges[-1][1]
            body_nodes = {ni for li in range(n_body)
                          for ni in self.graph.layers[li].nindex_out}
            for name in self._needed_nodes():
                ni = self.graph.node_names.index(name)
                if ni not in body_nodes:
                    raise ValueError(
                        f"pipeline_parallel: metric/extract node {name!r} "
                        "is not produced in the pipeline body")

    # Layers whose apply is correct on a local sequence shard under
    # shard_map (mha switches to the ring path, posembed offset-indexes
    # its table via ctx.seq_axis).
    _SP_SAFE_LAYERS = frozenset({
        "embed", "posembed", "layernorm", "mha", "ffn", "seqfc", "add",
        "lmloss", "moe", "relu", "sigmoid", "tanh", "softplus", "dropout",
        "share"})

    def _check_seq_parallel_ok(self) -> None:
        """seq_parallel (ring attention inside the config-driven step) is
        supported for pure sequence models; fail fast otherwise."""
        bad = [s.type for s in self.graph.layers
               if s.type not in self._SP_SAFE_LAYERS]
        if bad:
            raise ValueError(
                f"seq_parallel: layer types {sorted(set(bad))} are not "
                f"sequence-shardable")
        # model_parallel composes with seq_parallel: the shard_map is
        # partial-manual (('data','seq') manual, 'model' automatic), so
        # GSPMD still shards params/experts over 'model' inside the step
        if self.graph.extra_data_num:
            raise ValueError("seq_parallel does not support extra_data")
        c, y, S = self.graph.input_shape
        if (c, y) != (1, 1) or S % self._sp:
            raise ValueError(
                f"seq_parallel: input must be a flat (1,1,S) token node "
                f"with S divisible by {self._sp}, got {(c, y, S)}")
        # labels are pre-sliced per label_vec range on the host and each
        # slice is sharded over its width (token-aligned with the shard's
        # sequence chunk), so multiple slices are fine — each just needs a
        # width the seq axis divides
        for a, b in self.graph.label_range:
            if (b - a) % self._sp:
                raise ValueError(
                    f"seq_parallel: label_vec slice [{a},{b}) width "
                    f"{b - a} not divisible by {self._sp}")
        # metric[label,node] bindings on non-top nodes are supported: the
        # sp train/eval steps capture them with (data, seq) out-specs

    # -- model lifecycle ---------------------------------------------------
    def _param_pspecs(self, params=None):
        """GSPMD placement specs for params. Under pipeline parallelism
        the model axis is MANUAL inside the pp step (apply_stage slices
        planned weights per model shard), so 'model' sharding is disabled
        there; instead params+optimizer state shard AT REST over the
        'pipe' axis (FSDP-style: each leaf split on its first
        pipe-divisible dim, all-gathered once at step entry, gradients
        sliced back before the update). Per-device param+opt memory drops
        ~pp-fold — the memory headroom pipelining exists to buy — at the
        cost of one params all-gather per step, which for pp-scale models
        is small next to a step's activation traffic."""
        if self._pp > 1:
            return (self._pp_fsdp_specs(params)
                    if params is not None else {})
        pspecs = self.net.param_pspecs()
        if self._fsdp_axis:
            # FSDP-style at-rest sharding over a config-named axis
            # (rule-driven; ROADMAP item 4's reshard lever): each
            # large leaf takes the axis on its first free dividing
            # dim, GSPMD gathers in-step. Composes with tp specs.
            from .parallel.rules import add_fsdp
            pspecs = add_fsdp(
                pspecs, self.net.param_shapes(), self._fsdp_axis,
                int(self.mesh.mesh.shape.get(self._fsdp_axis, 1)),
                self.sharding_cfg.fsdp_min_size)
        return pspecs

    def _pp_fsdp_specs(self, params):
        """Per-leaf PartitionSpec tree: 'pipe' on the first dim divisible
        by the pipe degree, P() (replicated) when no dim divides (odd
        biases etc — a minority of bytes)."""
        from jax.sharding import PartitionSpec as P
        pp, pipe = self._pp, self.mesh.pipe_axis

        def leaf_spec(x):
            shape = np.shape(x)
            for d, s in enumerate(shape):
                if s and s % pp == 0:
                    return P(*([None] * d + [pipe]))
            return P()
        return jax.tree_util.tree_map(leaf_spec, params)

    @staticmethod
    def _spec_dim(spec, axis_name):
        for d, ax in enumerate(spec):
            if ax == axis_name or (isinstance(ax, tuple) and axis_name in ax):
                return d
        return None

    def _pp_gather_fn(self, specs):
        """(inside the manual pp shard_map) rebuild full param leaves from
        their pipe shards — one uniform all_gather per sharded leaf,
        ordered before every ring op that consumes it."""
        pipe = self.mesh.pipe_axis

        def g(x, spec):
            d = self._spec_dim(spec, pipe)
            if d is None:
                return x
            return jax.lax.all_gather(x, pipe, axis=d, tiled=True)
        return lambda tree: jax.tree_util.tree_map(
            g, tree, specs, is_leaf=lambda v: v is None)

    def _pp_scatter_fn(self, specs):
        """(inside the manual pp shard_map) slice this pipe member's shard
        out of a full (replicated-over-pipe) gradient leaf — collective-
        free; the custom-vjp schedule already psum'd the grads."""
        pipe = self.mesh.pipe_axis

        def s(x, spec):
            d = self._spec_dim(spec, pipe)
            if d is None:
                return x
            n = x.shape[d] // self._pp
            start = jax.lax.axis_index(pipe) * n
            return jax.lax.dynamic_slice_in_dim(x, start, n, axis=d)
        return lambda tree: jax.tree_util.tree_map(
            s, tree, specs, is_leaf=lambda v: v is None)

    def _place(self, params, net_state=None, opt_state=None):
        """Shard params (TP specs from the layers; size-1 model axis =
        replicated; pipe-FSDP specs under pp), mirror the sharding onto
        optimizer state, replicate the small net state. Placement goes
        through the rule-driven shard fns (parallel/rules.
        make_shard_and_gather_fns over the spec trees) — the same
        mechanism the elastic topology-change resume relies on, so a
        checkpoint written at one dp width restores losslessly at
        another (elastic/resume.py, tests/test_partition_rules.py)."""
        from .parallel.rules import make_shard_and_gather_fns
        pspecs = self._param_pspecs(params)
        shard_p, _ = make_shard_and_gather_fns(self.mesh, pspecs)
        out = [shard_p(params)]
        if net_state is not None:
            out.append(self.mesh.replicate(net_state))
        if opt_state is not None:
            shard_o, _ = make_shard_and_gather_fns(
                self.mesh, self.optimizer.state_pspecs(pspecs))
            out.append(shard_o(opt_state))
        return out[0] if len(out) == 1 else tuple(out)

    def _init_accum(self, params) -> None:
        if self.update_period > 1:
            self.accum = self.mesh.shard_params(
                jax.tree_util.tree_map(jnp.zeros_like, params),
                self._param_pspecs(params))

    def init_model(self) -> None:
        # one executable each, not one per leaf and op: a sequence model
        # of 60 leaves spent a minute of a cold start compiling them
        params, net_state = jax.jit(self.net.init)(self._base_key)
        self.params, self.net_state, self.opt_state = self._place(
            params, net_state,
            jax.jit(self.optimizer.init_state)(params))
        self._init_accum(params)

    def _checkpoint_sharded(self, path: str) -> bool:
        """Whether this save/exists check targets a shard-set round —
        the knob decides, but an explicit ``.model`` path (model_out,
        import tools) always stays a blob."""
        return bool(self.shard_ckpt) and not path.endswith(".model")

    def checkpoint_path(self, model_dir: str, round_counter: int) -> str:
        """Round path in this trainer's configured checkpoint format."""
        return ckpt.checkpoint_path(model_dir, round_counter,
                                    sharded=bool(self.shard_ckpt))

    def _shard_spec_map(self, params):
        """{flat_array_path: PartitionSpec} over the params AND
        optimizer-state groups — the same rule-driven spec trees
        placement uses, flattened to the checkpoint's path namespace so
        the shard writer chunks each leaf along its device-sharded dim
        (parallel/rules.py is the single source of truth for both)."""
        from .parallel.rules import tree_paths
        is_spec = lambda v: isinstance(v, tuple)
        out = {}
        pspecs = self._param_pspecs(params)
        for prefix, tree in (("params", pspecs),
                             ("opt", self.optimizer.state_pspecs(pspecs))):
            pairs, _ = tree_paths(tree, is_leaf=is_spec)
            for p, spec in pairs:
                out[f"{prefix}/{p}"] = spec
        return out

    def _ckpt_barrier(self, world: int):
        """Cross-rank 'all shards durable' barrier for the shard-set
        writer's manifest-last publish: the jax coordination-service
        wait (a TCP barrier — no device collective, so it is safe on
        the async writer thread while the main thread keeps dispatching
        steps). None on single-controller runs; None with a one-time
        warning when this jax exposes no distributed client (the
        manifest may then race a slower peer's shard write — readers
        quorum-reject the incomplete set either way)."""
        if world <= 1:
            return None
        try:
            from jax._src import distributed
            client = distributed.global_state.client
            if client is None:
                raise RuntimeError("no distributed client")
        except Exception as e:
            if not self._warned_no_ckpt_barrier:
                self._warned_no_ckpt_barrier = True
                print(f"WARNING: no coordination-service barrier for "
                      f"sharded checkpoint publishes "
                      f"({type(e).__name__}: {e}); a manifest may race "
                      "a slower rank's shard write (readers quorum-"
                      "reject the incomplete set)", flush=True)
            return None
        # pin the id NOW: under save_async the barrier runs on the
        # writer thread while the main thread keeps stepping, so a
        # late read of the live counters would give every rank a
        # different barrier name and time every publish out
        bid = f"cxxnet_ckpt_{self.round_counter}_{self._step_count}"

        def barrier():
            # id unique per save and identical across ranks (round +
            # step at save time pin it); a dead peer times out -> the
            # writer publishes anyway with a warning
            client.wait_at_barrier(bid, 120_000)
        return barrier

    @staticmethod
    def _stage_copy(tree):
        """Device-side copies of a checkpoint tree, dispatched
        asynchronously: fresh buffers the next step's donation cannot
        delete, so the device->host transfer itself can move to the
        async writer thread (save_async staging off the critical
        path). Non-device leaves copy on the host."""
        return jax.tree_util.tree_map(
            lambda x: jnp.copy(x) if isinstance(x, jax.Array)
            else np.array(x), tree)

    def save_model(self, path: str) -> None:
        # the gathers are cross-host collectives when params are
        # model-sharded: every rank must execute them; only rank 0
        # writes a blob, while shard mode has EVERY rank write its own
        # shard files (rank 0 adds the manifest, last)
        params = self.mesh.gather(self.params)
        opt = self.mesh.gather(self.opt_state)
        rank, world = jax.process_index(), jax.process_count()
        sharded = self._checkpoint_sharded(path)
        if not sharded and rank != 0:
            return
        kwargs = dict(
            structure_sig=self.graph.structure_signature(),
            round_counter=self.round_counter, epoch_counter=self.epoch_counter,
            step_count=self._step_count,
            lr_scale=self.optimizer.lr_scale)
        if sharded:
            from .ckpt_sharded import save_shard_set
            writer = save_shard_set
            kwargs.update(
                n_shards=self.shard_ckpt_shards or max(world, 1),
                spec_map=self._shard_spec_map(params),
                rank=rank, world=world,
                barrier=self._ckpt_barrier(world))
        else:
            writer = ckpt.save_model
        if not self.save_async:
            kwargs.update(params=params, net_state=self.net_state,
                          opt_state=opt)
            writer(path, **kwargs)
            return
        # drain the previous in-flight save BEFORE staging this one:
        # staging memory stays bounded at one checkpoint's copies
        self.wait_saves()
        if world > 1:
            # multi-controller: host copies on the caller thread (the
            # conservative path — staged device copies of global arrays
            # are backend-dependent); the file IO still overlaps
            kwargs.update(params=ckpt.jax_to_numpy(params),
                          opt_state=ckpt.jax_to_numpy(opt),
                          net_state=ckpt.jax_to_numpy(self.net_state))
        else:
            # fully-overlapped staging: device-side copies dispatch
            # async here (fresh buffers donation cannot delete); the
            # device->host transfer AND the archive write happen on the
            # background thread. Memory is bounded to ONE staged
            # checkpoint — wait_saves() below drains the previous save
            # before this one stages.
            kwargs.update(params=self._stage_copy(params),
                          opt_state=self._stage_copy(opt),
                          net_state=self._stage_copy(self.net_state))
        import threading
        err: List[BaseException] = []

        def _write():
            try:
                writer(path, **kwargs)
            except BaseException as e:      # surfaced by wait_saves()
                err.append(e)

        t = threading.Thread(target=_write, daemon=True)
        t.start()
        self._save_thread = (t, err)

    def wait_saves(self) -> None:
        """Join any in-flight async checkpoint write; re-raise its error
        (a silently missing checkpoint must not look like success)."""
        if self._save_thread is not None:
            t, err = self._save_thread
            t.join()
            self._save_thread = None
            if err:
                raise RuntimeError("async checkpoint write failed") from err[0]

    def load_model(self, path: str, verify: bool = True) -> None:
        self.wait_saves()     # never read a checkpoint mid-write
        self.load_blob(ckpt.load_model(path, verify=verify))

    def load_blob(self, blob: Dict[str, Any]) -> None:
        """Restore from an already-loaded checkpoint blob (the dict
        load_model/find_latest_valid produce) — callers that just read
        and VERIFIED the archive (resume scan, sentinel rollback) hand
        it over directly instead of paying a second full read."""
        ckpt.check_structure(blob["meta"], self.graph.structure_signature())
        opt = blob.get("opt") if blob.get("opt") is not None \
            else self.optimizer.init_state(blob["params"])
        # checkpoints are policy-portable: the fp32 masters restore as-is
        # and the fp16 loss-scaler subtree is injected/dropped to match
        # the CURRENT compute_dtype policy
        opt = self.optimizer.adapt_state(opt)
        self.params, self.net_state, self.opt_state = self._place(
            blob["params"], blob["state"], opt)
        self._init_accum(blob["params"])
        self.round_counter = blob["meta"]["round"]
        self.epoch_counter = blob["meta"]["epoch"]
        # restore the rng-stream position: step N's key re-derives as
        # fold_in(base_key, step_count) on next use, so a rolled-back run
        # replays the same dropout/mask stream it would have had (older
        # checkpoints lack the field — keep the live counter)
        sc = blob["meta"].get("step_count")
        if sc is not None:
            self._step_count = int(sc)
            self._rng_key = None
        # sentinel LR backoff survives the restore (absent in pre-v2
        # metas -> full LR); schedule caches key on VALUES, so drop them
        self.optimizer.lr_scale = float(blob["meta"].get("lr_scale", 1.0))
        self._sched_cache = None
        self._sched_stack_cache = None

    def rollback(self, path: str, blob: Optional[Dict[str, Any]] = None
                 ) -> int:
        """Restore params + optimizer state + net state + rng position +
        LR scale from a verified checkpoint — the sentinel's recovery
        action after a NaN/loss-spike step. Rides the exact fp32-master
        restore path load_model uses (policy-portable, sharded
        placement), then clears everything step-local a poisoned step
        may have touched. Pass the ``blob`` find_latest_valid already
        read+verified to skip a second full archive read. Returns the
        restored round."""
        self.wait_saves()
        if blob is not None:
            self.load_blob(blob)                  # re-zeros accum too
        else:
            self.load_model(path)
        self.sample_counter = 0
        self._last_loss = None
        self._pending_metric = None
        # step-local health state refers to the poisoned step — the
        # provenance walk (modelhealth.diagnose_nonfinite) runs BEFORE
        # the rollback; afterwards it must not linger
        self._last_health = None
        self._health_batch = None
        return self.round_counter

    def copy_model_from(self, path: str) -> None:
        """Finetune restore: name-matched layer copy from another model."""
        blob = ckpt.load_model(path)
        fresh = ckpt.jax_to_numpy(self.mesh.gather(self.params))
        merged = ckpt.copy_model_from(fresh, blob["params"],
                                      verbose=not self.silent)
        self.params = self._place(merged)

    def start_round(self, round_counter: int) -> None:
        self.round_counter = round_counter

    # -- weights API (reference SetWeight/GetWeight, nnet.h:69-91) ---------
    def _walk(self, tree, layer_name: str, tag: str):
        """Resolve a (layer, tag) pair; tag may be a dotted path into nested
        param dicts (e.g. 'q.wmat' for mha layers)."""
        node = tree[layer_name]
        for part in tag.split("."):
            node = node[part]
        return node

    def get_weight(self, layer_name: str, tag: str) -> np.ndarray:
        return np.asarray(self.mesh.gather(
            self._walk(self.params, layer_name, tag)))

    def set_weight(self, weight: np.ndarray, layer_name: str, tag: str) -> None:
        self.set_weights({(layer_name, tag): weight})

    def set_weights(self, updates) -> None:
        """Bulk weight assignment: one device->host gather and one placement
        for any number of tensors (``updates``: {(layer, dotted_tag): array}).
        """
        for (layer, tag), w in updates.items():
            cur = self._walk(self.params, layer, tag)
            if tuple(np.shape(w)) != tuple(cur.shape):
                raise ValueError(
                    f"set_weight {layer}.{tag}: shape {np.shape(w)} != "
                    f"{tuple(cur.shape)}")
        p = ckpt.jax_to_numpy(self.mesh.gather(self.params))
        for (layer, tag), w in updates.items():
            parts = tag.split(".")
            node = p[layer]
            for part in parts[:-1]:
                node = node[part]
            node[parts[-1]] = np.asarray(w, dtype=node[parts[-1]].dtype)
        self.params = self._place(p)

    def param_layer_names(self):
        """Top-level layer names present in the param tree."""
        return list(self.params.keys())

    def get_state(self, layer_name: str, tag: str) -> np.ndarray:
        """Read a layer-state entry (e.g. batch_norm running stats)."""
        return np.asarray(self._walk(self.net_state, layer_name, tag))

    def set_states(self, updates) -> None:
        """Bulk layer-state assignment (``updates``: {(layer, dotted_tag):
        array}) — the state analog of set_weights, used by weight importers
        to land e.g. Caffe BatchNorm running stats."""
        st = ckpt.jax_to_numpy(self.net_state)
        for (layer, tag), v in updates.items():
            parts = tag.split(".")
            node = st[layer]
            for part in parts[:-1]:
                node = node[part]
            cur = node[parts[-1]]
            if tuple(np.shape(v)) != tuple(np.shape(cur)):
                raise ValueError(
                    f"set_state {layer}.{tag}: shape {np.shape(v)} != "
                    f"{tuple(np.shape(cur))}")
            node[parts[-1]] = np.asarray(v, dtype=np.asarray(cur).dtype)
        self.net_state = self.mesh.replicate(st)

    # -- train step --------------------------------------------------------
    def _needed_nodes(self) -> List[str]:
        return sorted({n for n in self._metric_nodes if n is not None})

    def _shard_seq_batch(self, data, label=None):
        """Place batch arrays with the sequence axis sharded: token inputs
        (b,1,1,S), and the label pre-sliced per label_vec range with each
        slice sharded over its width — the host-side slicing is what lets
        every shard hold the token-aligned columns of EVERY slice (a
        global [a,b) slice of a width-sharded label would not be local)."""
        from jax.sharding import PartitionSpec as P
        out = [jax.device_put(data, self.mesh.named(
            P(self.mesh.data_axis, None, None, self.mesh.seq_axis)))]
        if label is not None:
            out.append(self._shard_seq_label(label))
        return out if len(out) != 1 else out[0]

    def _shard_seq_label(self, label):
        """Per-label_vec-range tuple of (data, seq)-sharded label slices —
        the form every sp step consumes (see _shard_seq_batch)."""
        from jax.sharding import PartitionSpec as P
        sh = self.mesh.named(P(self.mesh.data_axis, self.mesh.seq_axis))
        label = np.asarray(label)
        return tuple(
            jax.device_put(np.ascontiguousarray(label[:, a:b]), sh)
            for a, b in self.graph.label_range)

    def _make_sp_train_step(self, do_update: bool, chain: int = 0,
                            multi: bool = False):
        """Sequence-parallel train step: the whole step body runs under
        shard_map over the ('data','seq') mesh; mha layers take the ring
        path, gradients of replicated params are psum'd automatically by
        shard_map's transpose, and the loss is averaged across shards;
        the shard indices fold into the dropout rng so masks are
        independent per shard. ``chain`` > 0: lax.scan ``chain`` steps
        INSIDE the shard_map — over one fixed batch (update_chain;
        bench timing) or, with ``multi=True``, over ``chain`` DISTINCT
        stacked batches (update_chain_batches — fused-dispatch LM
        training, per-step schedules + eval_train metric nodes banked
        through the scan ys); per-step loss vector returned."""
        from jax.sharding import PartitionSpec as P
        net, opt, period = self.net, self.optimizer, self.update_period
        seq_axis, data_axis = self.mesh.seq_axis, self.mesh.data_axis
        rep = P()
        # multi chains bank per-step metric nodes (see _make_train_step)
        bank = bool(multi and self.eval_train)
        needed = self._needed_nodes() if (bank or not chain) else []
        capture = bool(needed)
        # model health rides the PLAIN sp step only; sp chains keep the
        # pre-health body (update_chain_batches warns once)
        health_on = self.health_on and not chain

        ranges = list(self.graph.label_range)

        def one(params, opt_state, net_state, accum, data, label, mask,
                rng, sched):
            # decorrelate dropout across shards: fold both shard indices
            # into the key (a replicated key would repeat masks per shard)
            rng_l = jax.random.fold_in(
                jax.random.fold_in(rng, jax.lax.axis_index(data_axis)),
                jax.lax.axis_index(seq_axis))
            lslices = dict(zip(ranges, label))

            def loss_fn(p):
                res = net.apply(p, net_state, data, None, mask, rng=rng_l,
                                train=True, seq_axis=seq_axis,
                                data_axis=data_axis, capture_nodes=capture,
                                label_slices=lslices, health=health_on)
                loss = jax.lax.pmean(
                    jax.lax.pmean(res.loss, seq_axis), data_axis)
                aux = (res.state, _collect_nodes(res, needed))
                return loss, aux + ((res.health,) if health_on else ())
            (loss, aux), grads = _scaled_value_and_grad(
                loss_fn, params, opt_state)
            if health_on:
                new_state, nodes, act = aux
            else:
                new_state, nodes = aux
                act = None
            # layer state computed from local shards (e.g. the MoE
            # load-balance aux loss) must leave the shard_map replicated
            new_state = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(
                    jax.lax.pmean(x, seq_axis), data_axis), new_state)
            p_old, o_old = params, opt_state
            params, opt_state, accum = _apply_grads(
                opt, period, do_update, params, opt_state, accum, grads,
                sched)
            if health_on:
                # grads/params are replicated by here (post-psum), so
                # their stats agree on every shard; the shard-LOCAL
                # activation stats reduce explicitly to the fleet view
                health = modelhealth.step_health(
                    grads, p_old, params, opt, o_old, opt_state,
                    modelhealth.reduce_island(act,
                                              (data_axis, seq_axis)))
                return (params, opt_state, new_state, accum, loss,
                        nodes, health, jax.random.fold_in(rng, 1))
            # the rng key chains device-side (no per-step host upload)
            return (params, opt_state, new_state, accum, loss, nodes,
                    jax.random.fold_in(rng, 1))

        if chain and multi:
            # sched stacked (k,) per tag rides the scan xs (per-step
            # schedules); per-step nodes bank through the ys when
            # eval_train is on
            def step(params, opt_state, net_state, data, label, mask,
                     rng, sched):
                def sbody(carry, xs):
                    p, o, s, r = carry
                    d, l, m, sc = xs
                    p, o, s, _a, loss, nodes, r = one(
                        p, o, s, {}, d, l, m, r, sc)
                    return (p, o, s, r), (loss, nodes if bank else {})
                (params, opt_state, net_state, rng), (losses, nodes) = \
                    jax.lax.scan(sbody,
                                 (params, opt_state, net_state, rng),
                                 (data, label, mask, sched))
                return params, opt_state, net_state, losses, nodes, rng
        elif chain:
            step = _chain_scan(one, chain)
        else:
            step = one
        node_spec = P(data_axis, seq_axis, None, None)
        nodes_spec = {k: node_spec for k in [_TOP] + needed}
        # PARTIAL-MANUAL shard_map: only ('data','seq') go manual; the
        # 'model' axis stays automatic, so GSPMD keeps tensor/expert
        # parallelism (per-layer param_pspecs) working INSIDE the
        # sequence-parallel step — this is what makes sp x tp compose
        data_spec = P(data_axis, None, None, seq_axis)
        lspec = tuple(P(data_axis, seq_axis) for _ in ranges)
        if chain and multi:
            # stacked batches: every batch leaf gains a leading
            # (unsharded) chain axis — including the banked per-step
            # metric nodes on the way out
            chain_nodes_spec = ({k: P(None, data_axis, seq_axis,
                                      None, None)
                                 for k in [_TOP] + needed} if bank else {})
            wrapped = jax.shard_map(
                step, mesh=self.mesh.mesh,
                in_specs=(rep, rep, rep,
                          P(None, data_axis, None, None, seq_axis),
                          tuple(P(None, data_axis, seq_axis)
                                for _ in ranges),
                          P(None, data_axis), rep, rep),
                out_specs=(rep, rep, rep, rep, chain_nodes_spec, rep),
                axis_names={data_axis, seq_axis})
        elif chain:
            wrapped = jax.shard_map(
                step, mesh=self.mesh.mesh,
                in_specs=(rep, rep, rep, data_spec, lspec,
                          P(data_axis), rep, rep),
                out_specs=(rep, rep, rep, rep, rep),
                axis_names={data_axis, seq_axis})
        else:
            # the health pytree (when carried) is replicated by
            # construction (see `one`): a single P() prefix covers it
            out_specs = (rep, rep, rep, rep, rep, nodes_spec) \
                + ((rep,) if health_on else ()) + (rep,)
            wrapped = jax.shard_map(
                step, mesh=self.mesh.mesh,
                in_specs=(rep, rep, rep, rep, data_spec, lspec,
                          P(data_axis), rep, rep),
                out_specs=out_specs,
                axis_names={data_axis, seq_axis})
        # chain: arg 3 is the batch — donate only the carried state
        return jax.jit(wrapped,
                       donate_argnums=(0, 1, 2) if chain else (0, 1, 2, 3))

    def _pp_row_specs(self, out_sd, node_sds):
        """out_specs for the pp steps' nodes dict: batch-sharded rows
        (dim 1 = tokens under sp) for the top output and every captured
        node — one definition for the train AND eval steps."""
        from jax.sharding import PartitionSpec as P
        data_axis, seq_axis = self.mesh.data_axis, self.mesh.seq_axis

        def row_spec(rank):
            if self._sp > 1 and rank >= 2:
                return P(data_axis, seq_axis, *([None] * (rank - 2)))
            return P(data_axis, *([None] * (rank - 1)))
        specs = {_TOP: row_spec(1 + len(out_sd.shape))}
        specs.update({name: row_spec(1 + len(sd.shape))
                      for name, sd in node_sds.items()})
        return specs

    @staticmethod
    def _pp_merge_banks(stats, capture, model_axis):
        """(inside the pp shard_map) pop each captured node's
        (M, mb, *dims) stat-sink bank, restore microbatch-major row
        order, and pmean over 'model' so replicated peers agree —
        shared by the train and eval steps."""
        nodes = {}
        for name in capture:
            bank = stats.pop("_node:" + name)
            nodes[name] = jax.lax.pmean(
                bank.reshape((-1,) + bank.shape[2:]), model_axis)
        return nodes

    def _pp_capture_plan(self, capture):
        """{name: (node_index, owner_stage, from_tail)} for captured
        nodes — owner = the LAST place producing the node (in-place
        rewrites included), where its final value exists. ``from_tail``
        marks nodes (re)written by a loss-tail layer: they bank from the
        tail's node map on the last stage, not from the body stage that
        first produced them (a tail ``softmax out->out`` rewrite must
        yield the post-softmax value, like the unsharded node map)."""
        plan = {}
        last_k = len(self._pp_ranges) - 1
        n_body = self._pp_ranges[-1][1]
        for name in capture:
            ni = self.graph.node_names.index(name)
            owner, from_tail = None, False
            for k, (lo, hi) in enumerate(self._pp_ranges):
                for li in range(lo, hi):
                    if ni in self.graph.layers[li].nindex_out:
                        owner = k
            for li in range(n_body, len(self.graph.layers)):
                if ni in self.graph.layers[li].nindex_out:
                    owner, from_tail = last_k, True
            if owner is None:
                raise ValueError(
                    f"pipeline_parallel: node {name!r} is not produced by "
                    "the pipeline body or the loss tail")
            plan[name] = (ni, owner, from_tail)
        return plan

    def _pp_probe_shapes(self, data_shape, train: bool = True,
                         cap_plan=None):
        """Per-microbatch boundary / final-output / batch-stat
        ShapeDtypeStructs for the pipeline ring register, via eval_shape
        over the stage chain. ``stats`` is the union of every stage's
        batch_norm moment structure (train only; empty at eval) plus one
        "_node:<name>" bank entry of shape (M, mb, ...) per captured
        node in ``cap_plan``."""
        mb = data_shape[0] // self.mesh.data_parallel // self._pp_microbatch
        rng0 = jax.random.PRNGKey(0)
        sp = self._sp
        carried = self.net._stage_carried
        # local microbatch geometry: rows / (dp * M); the trailing token
        # dim / sp under the sequence-parallel pipeline. Axes are NOT
        # bound during the probe (eval_shape runs outside shard_map);
        # local/global layer variants have identical local output shapes.
        local = list(data_shape[1:])
        if sp > 1:
            local[-1] //= sp
        seed = jax.ShapeDtypeStruct((mb,) + tuple(local), jnp.float32)
        cap_plan = cap_plan or {}
        M = self._pp_microbatch
        cap_at = lambda k: [ni for _n, (ni, o, ft) in cap_plan.items()
                            if o == k and not ft]
        tail_cap = sorted({ni for _n, (ni, o, ft) in cap_plan.items()
                           if ft})
        boundaries = []        # per boundary i: {node_index: sd} (with mb)
        stats: Dict[str, Any] = {}
        cap_sds: Dict[int, Any] = {}
        for k, (lo, hi) in enumerate(self._pp_ranges[:-1]):
            want = list(carried[k]) + [ni for ni in cap_at(k)
                                       if ni not in carried[k]]
            nd, st = jax.eval_shape(
                lambda p, s, x, _lo=lo, _hi=hi, _w=tuple(want):
                    self.net.apply_stage(_lo, _hi, p, x, rng0, train, s,
                                         want=list(_w)),
                self.params, self.net_state, seed)
            stats.update(st)
            cap_sds.update({ni: nd[ni] for ni in cap_at(k)})
            seed = {ni: nd[ni] for ni in carried[k]}
            boundaries.append(seed)
        lo, hi = self._pp_ranges[-1]
        n_body = hi
        tail_seeds = self.net._tail_seeds
        last_want = list(tail_seeds) + [
            ni for ni in cap_at(len(self._pp_ranges) - 1)
            if ni not in tail_seeds]

        msk = jax.ShapeDtypeStruct((mb,), jnp.float32)
        if sp > 1:
            lab = {(a, b): jax.ShapeDtypeStruct((mb, (b - a) // sp),
                                                jnp.float32)
                   for a, b in self.graph.label_range}

            def last(p, s, x, lslices, mask):
                nd, st = self.net.apply_stage(lo, hi, p, x, rng0, train, s,
                                              want=last_want)
                res = self.net.apply_tail(
                    n_body, p, {}, {ni: nd[ni] for ni in tail_seeds},
                    None, mask, rng0, train, label_slices=lslices,
                    want=tail_cap)
                return res.out, nd, res.nodes or {}, st
        else:
            lab = jax.ShapeDtypeStruct((mb, self.graph.label_width()),
                                       jnp.float32)

            def last(p, s, x, label, mask):
                nd, st = self.net.apply_stage(lo, hi, p, x, rng0, train, s,
                                              want=last_want)
                res = self.net.apply_tail(
                    n_body, p, {}, {ni: nd[ni] for ni in tail_seeds},
                    label, mask, rng0, train, want=tail_cap)
                return res.out, nd, res.nodes or {}, st
        out, nd_last, tail_nd, st = jax.eval_shape(
            last, self.params, self.net_state, seed, lab, msk)
        stats.update(st)
        cap_sds.update({ni: nd_last[ni]
                        for ni in cap_at(len(self._pp_ranges) - 1)})
        cap_sds.update(tail_nd)
        # "_aux:<layer>" sink entries are per-stage scalar losses (moe) —
        # they ride the schedule's differentiated scalar accumulator, not
        # the stats structure
        stats = {k: v for k, v in stats.items() if not k.startswith("_aux:")}
        # captured nodes bank per-microbatch slots through the stat sink
        for name, (ni, _owner, _ft) in cap_plan.items():
            sd = cap_sds[ni]
            stats["_node:" + name] = jax.ShapeDtypeStruct(
                (M,) + tuple(sd.shape), sd.dtype)
        strip = lambda a: jax.ShapeDtypeStruct(tuple(a.shape)[1:], a.dtype)
        return ([{ni: strip(sd) for ni, sd in b.items()}
                 for b in boundaries], strip(out), stats)

    def _pp_pipeline_fn(self, data_shape, train: bool, capture=()):
        """Local GPipe body (runs under shard_map): the stage schedule over
        the 'pipe' axis on this device's batch rows, with the loss layers
        folded into the LAST stage so all collectives chain off the ring
        (parallel/pipeline.py pipeline_apply_stages). ``state`` threads
        read-only into the stages (batch_norm running stats at eval);
        train-time BN moments come back in ``stats`` for the trainer's
        post-ring merge. ``capture``: body node names whose full-batch
        values the caller needs (metric bindings / extraction) — each
        owner stage banks its per-microbatch value into a "_node:<name>"
        stat-sink slot (``zeros(M,...).at[m].set(v)`` — the schedule's
        tick-sum over disjoint slots IS the bank, and the pipe-axis psum
        the merge). Known cost: the sink accumulator tick-adds the FULL
        (M, mb, ...) bank every tick (O(M + S) bank traversals per step
        vs the M slot-writes a dedicated scan carry would need) — fine
        for the eval path and for the occasional non-top train metric,
        not for routinely capturing large activations every step."""
        from .parallel.pipeline import pipeline_apply_stages
        net, ranges = self.net, self._pp_ranges
        n_body = ranges[-1][1]
        cap_plan = self._pp_capture_plan(capture)
        boundary_sds, out_sd, stats_sd = self._pp_probe_shapes(
            data_shape, train, cap_plan=cap_plan)
        # HETEROGENEOUS boundaries ride one flat max-size ring register:
        # each stage packs its boundary's CARRIED node set (every node
        # produced at or before the cut and consumed after it — so
        # cross-stage skip connections simply ride along) as flattened
        # concatenated segments, zero-padded to the max boundary size F;
        # the next stage unpacks its own carried dict. The ppermute
        # register stays uniform without constraining where stages may
        # cut. Register dtype: the common result type of every carried
        # node (f32 promotions are lossless; pad waste per boundary is
        # (F - sum(prod(shape)))/F of the ring bytes).
        carried = self.net._stage_carried
        all_sds = [sd for b in boundary_sds for sd in b.values()]
        reg_dtype = jnp.result_type(*[sd.dtype for sd in all_sds])
        flat_n = max(sum(int(np.prod(sd.shape)) for sd in b.values())
                     for b in boundary_sds)
        boundary_sd = jax.ShapeDtypeStruct((flat_n,), reg_dtype)

        def pack(i, nd):
            parts = [nd[ni].reshape(nd[ni].shape[0], -1).astype(reg_dtype)
                     for ni in carried[i]]
            flat = jnp.concatenate(parts, axis=1) if len(parts) > 1 \
                else parts[0]
            pad = flat_n - flat.shape[1]
            return jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat

        def unpack(reg, i):
            out, off = {}, 0
            for ni in carried[i]:
                sd = boundary_sds[i][ni]
                n = int(np.prod(sd.shape))
                out[ni] = reg[:, off:off + n].reshape(
                    reg.shape[0], *sd.shape).astype(sd.dtype)
                off += n
            return out
        pipe_axis, data_axis = self.mesh.pipe_axis, self.mesh.data_axis
        model_axis, tp = self.mesh.model_axis, self.mesh.model_parallel
        tp_plan = net.tp_manual_plan(tp, stage_ranges=ranges, train=train)
        tp_kw = dict(tp_axis=model_axis, tp_size=tp, tp_plan=tp_plan)
        M = self._pp_microbatch
        sp = self._sp
        seq_axis = self.mesh.seq_axis if sp > 1 else None
        label_ranges = list(self.graph.label_range)
        if sp > 1:
            # ring attention / global MoE routing inside the stages
            tp_kw = dict(tp_kw, seq_axis=seq_axis, data_axis=data_axis)

        def pad_stats(st):
            # every stage must return the SAME stats structure through the
            # lax.switch — fill the layers this stage doesn't own with zeros
            return {
                name: (st[name] if name in st else jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype), sub))
                for name, sub in stats_sd.items()}

        def split_aux(st):
            """Separate per-stage scalar losses ("_aux:<layer>", moe) from
            the batch-stat sink — scalars join the schedule's
            differentiated loss accumulator."""
            aux = jnp.zeros((), jnp.float32)
            rest = {}
            for k, v in st.items():
                if k.startswith("_aux:"):
                    aux = aux + v
                else:
                    rest[k] = v
            return aux, rest

        def body(p, x, label, mask, rng, state):
            mb = x.shape[0] // M
            # decorrelate dropout across data (and seq) shards, exactly as
            # the sp step does — a replicated key would repeat masks on
            # every shard's distinct rows/tokens. Model peers keep the
            # SAME key: they compute replicas/slices of identical rows and
            # divergent masks would break the manual-tp all-gather math.
            rng = jax.random.fold_in(rng,
                                     jax.lax.axis_index(data_axis))
            if sp > 1:
                rng = jax.random.fold_in(rng,
                                         jax.lax.axis_index(seq_axis))
            # the microbatch index folds in per microbatch below so masks
            # are independent across microbatches too
            cap_at = {}           # owner -> [(name, ni)], body-banked
            tail_caps = []        # [(name, ni)], banked post-tail
            for name, (ni, owner, ft) in cap_plan.items():
                if ft:
                    tail_caps.append((name, ni))
                else:
                    cap_at.setdefault(owner, []).append((name, ni))

            def bank_captured(st, nd, k, m, extra=()):
                # slot-bank this stage's captured node values; the
                # schedule's liveness gate zeroes drain-tick garbage and
                # its tick-sum accumulates the disjoint slots
                for name, ni in tuple(cap_at.get(k, ())) + tuple(extra):
                    v = nd[ni]
                    bank = jnp.zeros((M,) + v.shape, v.dtype)
                    st["_node:" + name] = bank.at[
                        jnp.clip(m, 0, M - 1)].set(v)
                return st

            def mid_fn(pp_, xx, m, k, _lo, _hi):
                seed = xx if k == 0 else unpack(xx, k - 1)
                want = list(carried[k]) + [ni for _n, ni in
                                           cap_at.get(k, ())
                                           if ni not in carried[k]]
                nd, st = net.apply_stage(_lo, _hi, pp_, seed,
                                         jax.random.fold_in(rng, m),
                                         train, state,
                                         want=want, **tp_kw)
                aux, st = split_aux(st)
                st = bank_captured(st, nd, k, m)
                # tie the scalar to a stage output so its JAX type is
                # varying even for stages with no aux loss — a bare
                # constant would type-mismatch the backward's varying
                # cotangent seed; the 0-coefficient contributes nothing
                first = nd[carried[k][0]]
                aux = aux + 0.0 * first.ravel()[0].astype(jnp.float32)
                return pack(k, nd), aux, pad_stats(st)
            fns = [
                (lambda pp_, xx, m, _k=k, _lo=lo, _hi=hi: mid_fn(
                    pp_, xx, m, _k, _lo, _hi))
                for k, (lo, hi) in enumerate(ranges[:-1])]
            lo, hi = ranges[-1]

            last_k = len(ranges) - 1

            tail_seeds = net._tail_seeds
            last_want = list(tail_seeds) + [ni for _n, ni in
                                            cap_at.get(last_k, ())
                                            if ni not in tail_seeds]

            tail_want = sorted({ni for _n, ni in tail_caps})

            def last_fn(pp_, xx, aux_mb, m):
                label_mb, mask_mb = aux_mb
                rng_m = jax.random.fold_in(rng, m)
                nd, st = net.apply_stage(lo, hi, pp_,
                                         unpack(xx, last_k - 1),
                                         rng_m, train, state,
                                         want=last_want, **tp_kw)
                aux, st = split_aux(st)
                seeds = {ni: nd[ni] for ni in tail_seeds}
                if sp > 1:
                    res = net.apply_tail(
                        n_body, pp_, {}, seeds, None, mask_mb,
                        rng_m, train,
                        label_slices=dict(zip(label_ranges, label_mb)),
                        seq_axis=seq_axis, data_axis=data_axis,
                        want=tail_want)
                else:
                    res = net.apply_tail(n_body, pp_, {}, seeds,
                                         label_mb, mask_mb, rng_m, train,
                                         want=tail_want)
                # tail-(re)written captures bank their post-tail values
                nd_full = dict(nd)
                nd_full.update(res.nodes or {})
                st = bank_captured(st, nd_full, last_k, m,
                                   extra=tail_caps)
                return res.out, res.loss + aux, pad_stats(st)
            fns.append(last_fn)
            # label: one (rows, W) array, or under sp a tuple of
            # width-sharded label_vec slices — reshape each leaf to
            # (M, mb, ...) for per-microbatch delivery
            aux = (jax.tree_util.tree_map(
                       lambda a: a.reshape(M, mb, *a.shape[1:]), label),
                   mask.reshape(M, mb))
            vary = (data_axis, model_axis) + ((seq_axis,) if sp > 1
                                              else ())
            top, loss_sum, stats = pipeline_apply_stages(
                fns, p, x, aux, pipe_axis, M, boundary_sd, out_sd,
                extra_vary_axes=vary,
                stats_sd=stats_sd)
            # each microbatch loss is a mean over its mb rows -> average
            # the M of them to match the non-pipelined per-batch loss
            return top, loss_sum / M, stats

        node_sds = {name: jax.ShapeDtypeStruct(
                        tuple(stats_sd["_node:" + name].shape)[2:],
                        stats_sd["_node:" + name].dtype)
                    for name in cap_plan}
        return body, out_sd, tp_plan, node_sds

    def _pp_bn_momenta(self) -> Dict[str, float]:
        """bn_momentum per moving-average batch_norm layer — the post-ring
        merge turns accumulated microbatch moments into ONE exact
        full-batch EMA update (matching the unsharded step's single
        per-batch update, not M per-microbatch ones)."""
        out: Dict[str, float] = {}
        for spec, layer in zip(self.graph.layers, self.net.layers):
            if (not spec.is_shared
                    and getattr(layer, "pp_batch_stats", False)
                    and layer.moving_avg):
                out[layer.name] = layer.bn_momentum
        return out

    def _make_pp_train_step(self, do_update: bool, data_shape,
                            chain: int = 0):
        """Pipeline-parallel train step. The WHOLE step body runs under
        one FULLY-MANUAL shard_map over ('data','pipe','model'). Tensor
        parallelism inside the stages is MANUAL — weight slices +
        output all-gathers from Network.tp_manual_plan, with the grads
        psum'd over 'model' here (GSPMD-auto model sharding would insert
        collectives inside the switch branches and deadlock). The
        custom-vjp backward schedule in pipeline_apply_stages produces
        the grads (see its docstring for why plain autodiff cannot).
        batch_norm layers normalize with microbatch-local statistics
        (the reference's own per-GPU BN semantics,
        batch_norm_layer-inl.hpp) while their running stats get one exact
        global-batch update merged across microbatches AND data shards.
        ``chain`` > 0: lax.scan ``chain`` steps over one fixed batch
        inside the shard_map (update_chain — one dispatch, no metric
        capture), returning the per-step loss vector."""
        from jax.sharding import PartitionSpec as P
        net, opt, period = self.net, self.optimizer, self.update_period
        pipe_axis, data_axis = self.mesh.pipe_axis, self.mesh.data_axis
        model_axis = self.mesh.model_axis
        sp, seq_axis = self._sp, self.mesh.seq_axis
        mean_axes = (data_axis, model_axis) + ((seq_axis,) if sp > 1
                                               else ())
        needed = (tuple(self._needed_nodes())
                  if self.eval_train and not chain else ())
        # the accumulator node (the FINAL layer's output, post loss tail)
        # already arrives via the schedule's out accumulator — a metric
        # bound to its NAME aliases it instead of banking a copy. Note
        # this is the overall-final node, not the top BODY node: a tail
        # rewrite (softmax out->out) or aux head makes them differ, and
        # the accumulator holds the post-tail value.
        top_name = self.graph.node_names[
            self.graph.layers[-1].nindex_out[0]]
        captured = tuple(n for n in needed if n != top_name)
        pipeline, out_sd, _, node_sds = self._pp_pipeline_fn(
            data_shape, train=True, capture=captured)
        bn_ema = self._pp_bn_momenta()
        # per-step deterministic state advances (insanity's annealing
        # counter): microbatches read the counter frozen, the trainer
        # ticks it ONCE here after the ring
        tick_layers = {
            layer.name: layer
            for spec, layer in zip(self.graph.layers, self.net.layers)
            if not spec.is_shared
            and getattr(layer, "pp_state_tick", False)}
        M = self._pp_microbatch
        rep = P()
        # at-rest FSDP over 'pipe': sharded leaves enter as local shards,
        # get all-gathered once up front, and the update runs on shards
        pspecs = self._pp_fsdp_specs(self.params)
        # state_pspecs marks replicated leaves None (shard_params' idiom);
        # shard_map in_specs need an explicit P() there
        opt_pspecs = jax.tree_util.tree_map(
            lambda v: P() if v is None else v,
            self.optimizer.state_pspecs(pspecs),
            is_leaf=lambda v: v is None)
        gather, scatter = self._pp_gather_fn(pspecs), \
            self._pp_scatter_fn(pspecs)

        def one(params, opt_state, net_state, accum, data, label, mask,
                rng, sched):
            full = gather(params)

            def loss_fn(p):
                top, loss, stats = pipeline(p, data, label, mask, rng,
                                            net_state)
                # pmean over 'model' BEFORE differentiating: the vjp then
                # seeds 1/tp per model peer, so the per-peer cotangent
                # contributions (routed through the manual all-gather
                # transposes) sum to exactly the true gradient — the same
                # seed/psum pairing the data axis uses (and the seq axis
                # under the sequence-parallel pipeline)
                return jax.lax.pmean(loss, mean_axes), (top, stats)
            (loss, (out, stats)), grads = _scaled_value_and_grad(
                loss_fn, full, opt_state)
            # manual-tp grad merge: the schedule's vjp psums every leaf
            # over 'model' (with the data axes) — planned leaves hold
            # partial (zero-padded slice) grads, unplanned leaves hold
            # 1/tp-scaled replicas; both sum to the exact gradient.
            # FSDP slice: the schedule's vjp left grads replicated over
            # 'pipe'; take this member's shard so the optimizer runs on
            # 1/pp of the state (collective-free)
            grads = scatter(grads)
            # model peers compute identical outputs (activations are
            # all-gathered); pmean makes them invariant for the out_specs
            out = jax.lax.pmean(out, model_axis)
            nodes = {_TOP: out}
            nodes.update(self._pp_merge_banks(stats, captured, model_axis))
            for name in needed:
                if name == top_name:
                    nodes[name] = out
            new_state = net_state
            if bn_ema:
                # stats arrive summed over the M live microbatches and
                # psum'd over 'pipe'; average across data shards too, then
                # E[x] = sum(mean_m)/M, Var = E[x^2] - E[x]^2 — exactly the
                # full-global-batch moments (equal-size microbatches)
                stats = jax.lax.pmean(stats, (data_axis, model_axis))
                new_state = dict(net_state)
                for name, mom in bn_ema.items():
                    mean = stats[name]["mean"] / M
                    # same tiny-negative cancellation guard as the BN
                    # layer's one-pass moments (layers/norm.py) — an
                    # unclamped -1e-8 here would EMA running_var
                    # negative and NaN the eval rsqrt
                    var = jnp.maximum(
                        stats[name]["sq"] / M - jnp.square(mean), 0.0)
                    st = net_state[name]
                    new_state[name] = {
                        "running_exp": st["running_exp"] * mom
                        + mean * (1 - mom),
                        "running_var": st["running_var"] * mom
                        + var * (1 - mom),
                    }
            if tick_layers:
                if new_state is net_state:
                    new_state = dict(net_state)
                for name, layer in tick_layers.items():
                    new_state[name] = layer.state_tick(net_state[name])
            # grads here are per-pipe FSDP shards (post-scatter): the fp16
            # overflow flag must be agreed over 'pipe' or members would
            # take different skip/apply branches
            params, opt_state, accum = _apply_grads(
                opt, period, do_update, params, opt_state, accum, grads,
                sched, finite_axes=(pipe_axis,))
            return (params, opt_state, new_state, accum, loss, nodes,
                    jax.random.fold_in(rng, 1))

        step = _chain_scan(one, chain) if chain else one
        if sp > 1:
            ds = P(data_axis, *([None] * (len(data_shape) - 2)), seq_axis)
            lspec = tuple(P(data_axis, seq_axis)
                          for _ in self.graph.label_range)
            axes = {data_axis, pipe_axis, model_axis, seq_axis}
        else:
            ds = P(data_axis, *([None] * (len(data_shape) - 1)))
            lspec = P(data_axis)
            axes = {data_axis, pipe_axis, model_axis}
        if chain:
            wrapped = jax.shard_map(
                step, mesh=self.mesh.mesh,
                in_specs=(pspecs, opt_pspecs, rep, ds, lspec,
                          P(data_axis), rep, rep),
                out_specs=(pspecs, opt_pspecs, rep, rep, rep),
                axis_names=axes)
            return jax.jit(wrapped, donate_argnums=(0, 1, 2))
        nodes_spec = self._pp_row_specs(out_sd, node_sds)
        for name in needed:
            if name == top_name:
                nodes_spec[name] = nodes_spec[_TOP]
        accum_spec = pspecs if period > 1 else rep
        wrapped = jax.shard_map(
            step, mesh=self.mesh.mesh,
            in_specs=(pspecs, opt_pspecs, rep, accum_spec, ds,
                      lspec, P(data_axis), rep, rep),
            out_specs=(pspecs, opt_pspecs, rep, accum_spec, rep,
                       nodes_spec, rep),
            axis_names=axes)
        return jax.jit(wrapped, donate_argnums=(0, 1, 2, 3))

    def _make_pp_eval_step(self, data_shape, extract=()):
        from jax.sharding import PartitionSpec as P
        data_axis, pipe_axis = self.mesh.data_axis, self.mesh.pipe_axis
        model_axis = self.mesh.model_axis
        sp, seq_axis = self._sp, self.mesh.seq_axis
        wanted = tuple(dict.fromkeys(
            tuple(self._needed_nodes()) + tuple(extract)))
        # accumulator alias: the FINAL layer's node (post tail) — see
        # _make_pp_train_step
        top_name = self.graph.node_names[
            self.graph.layers[-1].nindex_out[0]]
        capture = tuple(n for n in wanted if n != top_name)
        pipeline, out_sd, _, node_sds = self._pp_pipeline_fn(
            data_shape, train=False, capture=capture)
        pspecs = self._pp_fsdp_specs(self.params)
        gather = self._pp_gather_fn(pspecs)
        label_ranges = list(self.graph.label_range)

        def step(params, net_state, data):
            rows = data.shape[0]
            if sp > 1:           # local zero slices per label_vec range
                label = tuple(jnp.zeros((rows, (b - a) // sp), jnp.float32)
                              for a, b in label_ranges)
            else:
                label = jnp.zeros((rows, self.graph.label_width()),
                                  jnp.float32)
            mask = jnp.ones((rows,), jnp.float32)
            top, _, stats = pipeline(gather(params), data, label, mask,
                                     jax.random.PRNGKey(0), net_state)
            nodes = {_TOP: jax.lax.pmean(top, model_axis)}
            nodes.update(self._pp_merge_banks(stats, capture, model_axis))
            for name in wanted:
                if name == top_name:
                    nodes[name] = nodes[_TOP]
            return nodes

        if sp > 1:
            ds = P(data_axis, *([None] * (len(data_shape) - 2)), seq_axis)
            axes = {data_axis, pipe_axis, model_axis, seq_axis}
        else:
            ds = P(data_axis, *([None] * (len(data_shape) - 1)))
            axes = {data_axis, pipe_axis, model_axis}
        nodes_spec = self._pp_row_specs(out_sd, node_sds)
        for name in wanted:
            if name == top_name:
                nodes_spec[name] = nodes_spec[_TOP]
        wrapped = jax.shard_map(step, mesh=self.mesh.mesh,
                                in_specs=(pspecs, P(), ds),
                                out_specs=nodes_spec,
                                axis_names=axes)
        return jax.jit(wrapped)

    def _make_train_step(self, do_update: bool, chain: int = 0,
                         multi: bool = False):
        """Standard (GSPMD dp/tp) train step. ``chain`` > 0: k steps
        fused into ONE dispatch via lax.scan — on one fixed batch
        (update_chain; bench timing, no metric capture), or with
        ``multi=True`` over k DISTINCT stacked batches
        (update_chain_batches; real training with the per-dispatch host
        overhead amortized k-fold, per-step schedules + eval_train
        metric nodes riding the scan). Exists because a small model's
        step is shorter than one dispatch from the host. The rng chains
        per-step exactly as ``update`` does."""
        net, opt, period = self.net, self.optimizer, self.update_period
        # multi chains (real training) bank per-step metric nodes through
        # the scan ys so eval_train composes with train_chain; fixed-batch
        # chains (bench timing) still discard them
        bank = bool(multi and self.eval_train)
        needed = self._needed_nodes() if (bank or not chain) else []
        capture = bool(needed)
        # model health rides plain steps and multi chains; fixed-batch
        # (bench) chains never carry it. health_on False leaves every
        # closure below on the exact pre-health path.
        health_on = self.health_on and (not chain or multi)
        reduce_keys, drop_top = self._reduce_keys, self._drop_top

        def fwd_bwd(params, opt_state, net_state, data, label, mask,
                    extra, rng):
            # ONE forward/backward body shared by the plain and the
            # accumulating chain step — keeps the two numerically locked
            # (opt_state is read-only here: the fp16 loss scale rides it)
            def loss_fn(p):
                res = net.apply(p, net_state, data, label, mask,
                                extra_data=extra, rng=rng, train=True,
                                capture_nodes=capture, health=health_on)
                nodes = _collect_nodes(res, needed, reduce_keys, drop_top)
                moe = _layer_stats(res.state)
                if moe and not chain:
                    nodes[_MOE] = moe
                dsa = _layer_stats(res.state, "dsa_stats")
                if dsa and not chain:
                    nodes[_DSA] = dsa
                aux = (res.state, nodes)
                return res.loss, aux + ((res.health,) if health_on
                                        else ())
            return _scaled_value_and_grad(loss_fn, params, opt_state)

        def one(params, opt_state, net_state, accum, data, label, mask,
                extra, rng, sched):
            # input_fold: a (uint8, mean, factor) data tuple normalizes
            # here, in-trace (fixed-batch chains re-fold per scan step —
            # that IS the fused read: u8 in, compute dtype out)
            data = _fold_input(data, net)
            if health_on:
                (loss, (new_state, nodes, act)), grads = fwd_bwd(
                    params, opt_state, net_state, data, label, mask,
                    extra, rng)
                p_old, o_old = params, opt_state
                params, opt_state, accum = _apply_grads(
                    opt, period, do_update, params, opt_state, accum,
                    grads, sched)
                health = modelhealth.step_health(
                    grads, p_old, params, opt, o_old, opt_state, act)
                return (params, opt_state, new_state, accum, loss,
                        nodes, health, jax.random.fold_in(rng, 1))
            (loss, (new_state, nodes)), grads = fwd_bwd(
                params, opt_state, net_state, data, label, mask, extra, rng)
            params, opt_state, accum = _apply_grads(
                opt, period, do_update, params, opt_state, accum, grads,
                sched)
            # the rng key chains device-side (no per-step host upload)
            return (params, opt_state, new_state, accum, loss, nodes,
                    jax.random.fold_in(rng, 1))

        if chain and multi and period > 1:
            # gradient accumulation INSIDE the chain (the reference's
            # update_period memory recipe, e.g. AlexNet's batch-256 via
            # 2 x 128): the accumulator and the sample counter ride the
            # scan carry, and the optimizer applies under lax.cond on
            # the period boundary — chains need not align with periods
            def one_acc(p, o, s, a, c, d, l, m, e, r, sc):
                if health_on:
                    (loss, (new_state, nodes, act)), grads = fwd_bwd(
                        p, o, s, d, l, m, e, r)
                else:
                    (loss, (new_state, nodes)), grads = fwd_bwd(
                        p, o, s, d, l, m, e, r)
                    act = None
                with _optimizer_scope():
                    a = jax.tree_util.tree_map(jnp.add, a, grads)

                p_old, o_old = p, o
                p, o, a = jax.lax.cond(
                    (c + 1) % period == 0,
                    lambda args: _apply_accum(opt, period, args[0],
                                              args[1], args[2], args[3]),
                    lambda args: (args[0], args[1], args[2]),
                    (p, o, a, sc))
                health = (modelhealth.step_health(
                    grads, p_old, p, opt, o_old, o, act)
                    if health_on else None)
                return (p, o, new_state, a, c + 1, loss, nodes, health,
                        jax.random.fold_in(r, 1))

            def step(params, opt_state, net_state, accum, cnt0, data,
                     label, mask, extra, rng, sched):
                # fold the whole stacked chain once BEFORE the scan: the
                # (k,B,...) uint8 tuple's mean/factor have no chain axis
                # to scan over, and one k-sized fold keeps the per-step
                # reads in the compute dtype
                data = _fold_input(data, net)

                def sbody(carry, xs):
                    p, o, s, a, c, r = carry
                    d, l, m, e, sc = xs
                    p, o, s, a, c, loss, nodes, health, r = one_acc(
                        p, o, s, a, c, d, l, m, e, r, sc)
                    ys = (loss, nodes if bank else {}) \
                        + ((health,) if health_on else ())
                    return (p, o, s, a, c, r), ys
                (params, opt_state, net_state, accum, _c, rng), ys = \
                    jax.lax.scan(
                        sbody,
                        (params, opt_state, net_state, accum, cnt0, rng),
                        (data, label, mask, extra, sched))
                if health_on:
                    losses, nodes, healths = ys
                    # the chain's LAST step is the probe's view (stats
                    # are per-step; the newest is what the sync reads)
                    health = jax.tree_util.tree_map(lambda v: v[-1],
                                                    healths)
                    return (params, opt_state, net_state, losses, nodes,
                            health, accum, rng)
                losses, nodes = ys
                return (params, opt_state, net_state, losses, nodes,
                        accum, rng)
            return jax.jit(step, donate_argnums=(0, 1, 2, 3))
        if chain and multi:
            # sched arrives stacked (k,) per tag — per-step LR/momentum
            # ride the scan xs, so chained training follows the same
            # schedule trajectory as k plain update() calls
            def step(params, opt_state, net_state, data, label, mask,
                     extra, rng, sched):
                data = _fold_input(data, net)   # once, pre-scan (above)

                def sbody(carry, xs):
                    p, o, s, r = carry
                    d, l, m, e, sc = xs
                    if health_on:
                        p, o, s, _a, loss, nodes, health, r = one(
                            p, o, s, {}, d, l, m, e, r, sc)
                        return (p, o, s, r), (loss,
                                              nodes if bank else {},
                                              health)
                    p, o, s, _a, loss, nodes, r = one(
                        p, o, s, {}, d, l, m, e, r, sc)
                    return (p, o, s, r), (loss, nodes if bank else {})
                (params, opt_state, net_state, rng), ys = \
                    jax.lax.scan(sbody,
                                 (params, opt_state, net_state, rng),
                                 (data, label, mask, extra, sched))
                if health_on:
                    losses, nodes, healths = ys
                    health = jax.tree_util.tree_map(lambda v: v[-1],
                                                    healths)
                    return (params, opt_state, net_state, losses, nodes,
                            health, rng)
                losses, nodes = ys
                return params, opt_state, net_state, losses, nodes, rng
            return jax.jit(step, donate_argnums=(0, 1, 2))
        if chain:
            def step(params, opt_state, net_state, data, label, mask,
                     extra, rng, sched):
                bound = lambda p, o, s, a, d, l, m, r, sc: one(
                    p, o, s, a, d, l, m, extra, r, sc)
                return _chain_scan(bound, chain)(
                    params, opt_state, net_state, data, label, mask,
                    rng, sched)
            return jax.jit(step, donate_argnums=(0, 1, 2))
        return jax.jit(one, donate_argnums=(0, 1, 2, 3))

    def update_chain(self, batch: DataBatch, k: int) -> "jax.Array":
        """Run ``k`` train steps on one (fixed) batch in a single device
        dispatch; returns the per-step loss vector (device array — fetch
        to sync). Works in std, sp, and pp modes (the scan wraps the
        modal step body inside its shard_map); composes with dp/tp
        shardings. Not supported: gradient accumulation
        (``update_period``) and train-metric capture. LR/momentum
        schedules are evaluated once at chain start and held for the k
        steps."""
        assert self.params is not None, "call init_model() first"
        if k <= 0:
            raise ValueError(f"update_chain: k must be >= 1, got {k}")
        if self.update_period > 1:
            raise ValueError("update_chain: update_period accumulation "
                             "does not chain")
        mode = "pp" if self._pp > 1 else "sp" if self._sp > 1 else "std"
        key = ("chain", k, mode,
               np.shape(batch.data) if mode == "pp" else None)
        if key not in self._train_step_fns:
            if mode == "pp":
                fn = self._make_pp_train_step(True, np.shape(batch.data),
                                              chain=k)
            elif mode == "sp":
                fn = self._make_sp_train_step(True, chain=k)
            else:
                fn = self._make_train_step(True, chain=k)
            self._train_step_fns[key] = fn
        mask = self._mask(batch)
        if self._rng_key is None:
            self._rng_key = self._rng_key_at(self._step_count)
        staged = self.stage_batch(batch)
        data = self._fold_args(staged) if mode == "std" else staged.data
        args = (self.params, self.opt_state, self.net_state, data,
                staged.label, mask) \
            + ((tuple(staged.extra_data),) if mode == "std" else ()) \
            + (self._rng_key, self._sched_scalars())
        (self.params, self.opt_state, self.net_state, losses,
         self._rng_key) = self._train_step_fns[key](*args)
        self._last_loss = losses[-1]
        self._step_count += k
        self.sample_counter = 0
        self.epoch_counter += k
        self._report_selection()
        return losses

    def update_chain_batches(self, batches) -> "jax.Array":
        """Run len(batches) train steps on DISTINCT batches in one device
        dispatch (lax.scan over the stacked batch arrays) — real
        training with the per-dispatch host overhead amortized, for
        small models (task driver knob ``train_chain = k``). Same math
        as k sequential ``update()``
        calls: per-batch padding masks apply, the rng chains per step,
        per-step LR/momentum schedule values ride the scan, with
        ``eval_train`` the per-step metric nodes bank through the scan
        ys (fetched lazily, like update()'s deferred metric), and in
        std mode ``update_period`` accumulation rides the scan carry
        (chains need not align with period boundaries). std (dp/tp)
        and sp modes; no accumulation under sp, and no pp (pp models
        are dispatch-floor-irrelevant — their steps are tens of ms)."""
        assert self.params is not None, "call init_model() first"
        self.last_drain_s = 0.0
        k = len(batches)
        if k == 0:
            raise ValueError("update_chain_batches: empty batch list")
        if self._pp > 1:
            raise ValueError("update_chain_batches: std/sp modes only")
        if self.update_period > 1 and self._sp > 1:
            raise ValueError("update_chain_batches: update_period "
                             "accumulation chains in std mode only")
        from jax.sharding import PartitionSpec as P
        da, sa = self.mesh.data_axis, self.mesh.seq_axis

        def put(arr, spec):
            return jax.device_put(arr, self.mesh.named(spec))

        def put_rows(arr, ndim_tail):
            return put(arr, P(None, da, *([None] * ndim_tail)))

        # one normalize over the stacked array — all batches must share
        # the deferred-norm constants (same iterator => same metadata)
        def check_norms():
            norms = {(None if b.norm is None else
                      (np.asarray(b.norm.get("mean"),
                                  np.float32).tobytes()
                       if b.norm.get("mean") is not None else None,
                       float(b.norm.get("divideby", 1.0)),
                       float(b.norm.get("scale", 1.0))))
                     for b in batches}
            if len(norms) != 1:
                raise ValueError("update_chain_batches: batches carry "
                                 "different deferred-norm metadata")
        masks = np.ones((k, batches[0].batch_size), np.float32)
        for i, b in enumerate(batches):
            if b.num_batch_padd:
                masks[i, b.batch_size - b.num_batch_padd:] = 0.0
        masks = put_rows(masks, 0)
        if self._sp > 1:
            # stacked sp staging (_shard_seq_batch per batch, + chain
            # axis): token dim sharded over 'seq', labels pre-sliced per
            # label_vec range with each slice (k, B, Wr) (data, seq)
            check_norms()
            data = put(np.stack([np.asarray(b.data) for b in batches]),
                       P(None, da, None, None, sa))
            data = self._device_normalize(data, batches[0])
            labs = [np.asarray(b.label) for b in batches]
            label = tuple(
                put(np.stack([np.ascontiguousarray(l[:, a:b_])
                              for l in labs]), P(None, da, sa))
                for a, b_ in self.graph.label_range)
            args_extra = ()
            key = ("chainb", k, "sp", bool(self.eval_train))
            maker = lambda: self._make_sp_train_step(True, chain=k,
                                                     multi=True)
        else:
            data = put_rows(
                np.stack([np.asarray(b.data) for b in batches]),
                np.ndim(batches[0].data) - 1)
            check_norms()
            if self._fold_capable(batches[0]):
                # input_fold: the stacked uint8 chain enters the step
                # raw; the multi-chain step folds it once before its
                # scan (_make_train_step)
                mean, factor = self._fold_consts(batches[0].norm)
                data = (data, mean, factor)
            else:
                data = self._device_normalize(data, batches[0])
            label = put_rows(
                np.stack([np.asarray(b.label) for b in batches]), 1)
            n_extra = len(batches[0].extra_data)
            args_extra = (tuple(
                put_rows(np.stack([np.asarray(b.extra_data[j])
                                   for b in batches]),
                         np.ndim(batches[0].extra_data[j]) - 1)
                for j in range(n_extra)),)
            key = ("chainb", k, n_extra, bool(self.eval_train))
            maker = lambda: self._make_train_step(True, chain=k,
                                                  multi=True)
        period = self.update_period
        if period > 1:
            key = key + ("acc",)
        if key not in self._train_step_fns:
            self._train_step_fns[key] = maker()
        if self._rng_key is None:
            self._rng_key = self._rng_key_at(self._step_count)
        sched = self._sched_stack(k)
        # the sp chain bodies keep the pre-health path (see
        # _make_sp_train_step); std multi chains carry the health tree
        health_here = self.health_on and self._sp == 1
        if self.health_on and not health_here \
                and not self._warned_health_chain:
            self._warned_health_chain = True
            print("WARNING: health=1 does not ride sp train chains; "
                  "model-health stats are unavailable for this "
                  "dispatch family", flush=True)
        if period > 1:
            # accumulator + sample counter thread through the chain so
            # period boundaries need not align with chain boundaries
            # counter scalar cached by value (usually 0 when chains
            # align with periods) — same no-reupload idiom as
            # _sched_scalars
            if self._cnt_cache is None \
                    or self._cnt_cache[0] != self.sample_counter:
                self._cnt_cache = (self.sample_counter,
                                   jnp.int32(self.sample_counter))
            out = self._train_step_fns[key](
                 self.params, self.opt_state, self.net_state, self.accum,
                 self._cnt_cache[1], data, label, masks,
                 *args_extra, self._rng_key, sched)
            if health_here:
                (self.params, self.opt_state, self.net_state, losses,
                 nodes, self._last_health, self.accum,
                 self._rng_key) = out
            else:
                (self.params, self.opt_state, self.net_state, losses,
                 nodes, self.accum, self._rng_key) = out
        else:
            out = self._train_step_fns[key](
                 self.params, self.opt_state, self.net_state, data,
                 label, masks, *args_extra, self._rng_key, sched)
            if health_here:
                (self.params, self.opt_state, self.net_state, losses,
                 nodes, self._last_health, self._rng_key) = out
            else:
                (self.params, self.opt_state, self.net_state, losses,
                 nodes, self._rng_key) = out
        self._last_loss = losses[-1]
        self._step_count += k
        total = self.sample_counter + k
        self.sample_counter = total % period
        self.epoch_counter += total // period
        if self.eval_train and nodes:
            self._drain_pending_metric()
            self._pending_metric = (nodes, list(batches))
        self._report_selection()
        return losses

    def _rng_key_at(self, step: int):
        """The step rng for step number ``step``, placed on the mesh
        like every other carried argument: the key a step RETURNS is
        typed with the mesh, and a first key without it would make the
        second call a tracing-cache miss — the whole step compiled
        twice."""
        return self.mesh.replicate(jax.random.fold_in(self._base_key, step))

    def _report_selection(self) -> None:
        """Say once, after the first train step has been traced, which
        attention implementation and which grouped product the sites
        that choose one took."""
        if self._selection_reported or not self.net.fused_log:
            return
        self._selection_reported = True
        if not self.silent:
            from .ops.fused import selection_summary
            print(selection_summary(self.net.fused_log), flush=True)

    def _sched_scalars(self):
        """Schedule values as traced device scalars (no recompile when they
        change). Cached by value: re-uploading identical scalars every step
        costs a host->device transfer each."""
        sched = self.optimizer.schedules(self.epoch_counter)
        key = tuple(sorted((tag, lr, mom)
                           for tag, (lr, mom) in sched.items()))
        if self._sched_cache is None or self._sched_cache[0] != key:
            self._sched_cache = (key, {
                tag: (jnp.float32(lr), jnp.float32(mom))
                for tag, (lr, mom) in sched.items()})
        return self._sched_cache[1]

    def _sched_stack(self, k: int):
        """Per-step schedule values for a k-step chain, stacked (k,) per
        tag — step i of the chain sees the schedule of the epoch counter
        it would have under k sequential update() calls (the counter
        advances once per APPLIED update, i.e. every update_period
        steps). Cached by value (constant schedules re-use one device
        upload)."""
        per = self.update_period
        scheds = [self.optimizer.schedules(
            self.epoch_counter + (self.sample_counter + i) // per)
            for i in range(k)]
        key = tuple(sorted(
            (tag,) + tuple(v for s in scheds for v in s[tag])
            for tag in scheds[0]))
        if self._sched_stack_cache is None \
                or self._sched_stack_cache[0] != key:
            self._sched_stack_cache = (key, {
                tag: (jnp.asarray([s[tag][0] for s in scheds],
                                  jnp.float32),
                      jnp.asarray([s[tag][1] for s in scheds],
                                  jnp.float32))
                for tag in scheds[0]})
        return self._sched_stack_cache[1]

    def _get_train_step(self, do_update: bool, batch: DataBatch):
        """Resolve (and cache) the jitted train step for the active
        parallelism mode — one dispatch point for update() and the cost
        probe."""
        # pp wins when both are set: the pp step runs the seq schedule
        # inside its stages (pp x sp)
        mode = "pp" if self._pp > 1 else "sp" if self._sp > 1 else "std"
        # the pp body closes over probe shapes derived from the batch shape;
        # std/sp recompile via jit shape polymorphism, pp must key on it
        key = (do_update, mode, np.shape(batch.data) if mode == "pp" else None)
        if key not in self._train_step_fns:
            if mode == "sp":
                fn = self._make_sp_train_step(do_update)
            elif mode == "pp":
                fn = self._make_pp_train_step(do_update,
                                              np.shape(batch.data))
            else:
                fn = self._make_train_step(do_update)
            self._train_step_fns[key] = fn
        return self._train_step_fns[key]

    def stage_batch(self, batch: DataBatch, for_eval: bool = False
                    ) -> DataBatch:
        """Traced wrapper over :meth:`_stage_batch` — the host->device
        transfer span ("train.h2d_stage"; dispatch-side duration, the
        copies themselves are async). Entered twice a step: from
        ``prefetch_device`` with the host batch, and from ``update()``
        with the staged one (a pass-through). The shared no-op span
        where the tracer does not record ``train`` spans."""
        with TRACER.span("train.h2d_stage", cat="train"):
            return self._stage_batch(batch, for_eval)

    def _stage_batch(self, batch: DataBatch, for_eval: bool = False
                     ) -> DataBatch:
        """Asynchronously place a host batch on the mesh: shard + deferred
        uint8 normalize, all dispatched without blocking (jax.device_put
        and jitted calls return futures). Staging batch N+1 while step N
        runs overlaps the H2D copy with compute — the reason the
        reference's ThreadBufferIterator exists
        (iter_batch_proc-inl.hpp:132-220), extended here to the device
        boundary. ``update``/``predict`` accept staged batches as-is.
        ``for_eval`` stages only the data: eval steps never consume the
        label/extra arrays (metrics read labels host-side), so uploading
        them would waste the bandwidth the prefetch exists to hide."""
        if isinstance(batch.data, jax.Array):
            # already staged — but a mode-unaware caller (e.g. bench's
            # device-resident batches) may have staged the label as one
            # array where the sp steps need the per-label_vec-range tuple
            # of seq-sharded slices; restage just the label, cached per
            # caller-held label object (one host round-trip total)
            if (self._sp > 1 and not for_eval and batch.label is not None
                    and not isinstance(batch.label, tuple)):
                # cache holds the label OBJECT (identity key + keep-alive:
                # a bare id() could be reused by a new array after GC and
                # silently serve stale slices)
                if self._sp_label_cache is None \
                        or self._sp_label_cache[0] is not batch.label:
                    host = np.asarray(batch.label)
                    self._sp_label_cache = (
                        batch.label, self._shard_seq_label(host), host)
                _, sliced, host = self._sp_label_cache
                batch = DataBatch(
                    data=batch.data, label=sliced,
                    num_batch_padd=batch.num_batch_padd,
                    inst_index=batch.inst_index,
                    extra_data=batch.extra_data, norm=batch.norm,
                    host_label=host)
            return batch
        if for_eval:
            data = (self._shard_seq_batch(batch.data) if self._sp > 1
                    else self.mesh.shard_batch(batch.data))
            # extra_data IS consumed by the std eval step — stage it;
            # _eval_nodes's re-shard of device arrays is a no-op
            extra = [self.mesh.shard_batch(e) for e in batch.extra_data]
            return DataBatch(data=self._device_normalize(data, batch),
                             label=batch.label,
                             num_batch_padd=batch.num_batch_padd,
                             inst_index=batch.inst_index,
                             extra_data=extra, norm=None)
        if self._sp > 1:
            data, label = self._shard_seq_batch(batch.data, batch.label)
            data = self._device_normalize(data, batch)
            fold = False
        else:
            data, label = self.mesh.shard_batch(batch.data, batch.label)
            # input_fold: ship the uint8 payload as-is and keep the norm
            # metadata — the normalize happens in-trace at dispatch
            # (_fold_args); everything else normalizes eagerly here
            fold = self._fold_capable(batch)
            if not fold:
                data = self._device_normalize(data, batch)
        extra = [self.mesh.shard_batch(e) for e in batch.extra_data]
        return DataBatch(data=data, label=label,
                         num_batch_padd=batch.num_batch_padd,
                         inst_index=batch.inst_index, extra_data=extra,
                         norm=batch.norm if fold else None,
                         host_label=batch.label)

    def prefetch_device(self, it, depth: int = 2, for_eval: bool = False):
        """Wrap a batch iterable so ``depth`` batches are staged on-device
        ahead of consumption (device-side double buffering)."""
        from collections import deque
        q: "deque" = deque()
        for b in it:
            q.append(self.stage_batch(b, for_eval=for_eval))
            if len(q) >= depth:
                yield q.popleft()
        while q:
            yield q.popleft()

    def update(self, batch: DataBatch) -> None:
        """One minibatch forward/backward(+update) — reference Update
        (nnet_impl-inl.hpp:157-202). ``batch`` may be a host batch or one
        staged by ``stage_batch``/``prefetch_device``."""
        assert self.params is not None, "call init_model() first"
        t_dispatch0 = time.perf_counter()
        self.last_drain_s = 0.0
        do_update = (self.sample_counter + 1) % self.update_period == 0 \
            if self.update_period > 1 else True
        step = self._get_train_step(do_update, batch)
        mask = self._mask(batch)
        if self._rng_key is None:
            self._rng_key = self._rng_key_at(self._step_count)
        accum_in = self.accum if self.update_period > 1 else {}
        staged = self.stage_batch(batch)
        # _fold_args: plain staged array, or the input_fold tuple whose
        # normalize happens inside the step (no-op for sp/pp staging,
        # which normalized eagerly)
        data, label = self._fold_args(staged), staged.label
        rng_in = self._rng_key
        # std steps take the extra-data tuple; sp/pp staging has none
        extra = () if self._pp > 1 or self._sp > 1 \
            else (tuple(staged.extra_data),)
        args = (self.params, self.opt_state, self.net_state, accum_in,
                data, label, mask) + extra + (self._rng_key,
                                              self._sched_scalars())
        if step is not self._described_step:
            self._describe_step(step, args)
        out = step(*args)
        if self.health_on and self._pp <= 1:
            (self.params, self.opt_state, self.net_state, accum, loss,
             nodes, self._last_health, self._rng_key) = out
            if self._sp <= 1:
                # stash the step's inputs (device references, one batch)
                # so the one-shot NaN-provenance walk can re-run this
                # exact forward/backward
                # (modelhealth.diagnose_nonfinite)
                self._health_batch = (data, label, mask, extra[0], rng_in)
        else:
            (self.params, self.opt_state, self.net_state, accum, loss,
             nodes, self._rng_key) = out
        if self.update_period > 1:
            self.accum = accum
        self._last_loss = loss
        self._report_selection()
        if failpoints.fire("device.step"):
            # injected bad step: poison params AND the loss exactly the
            # way a real divergent/NaN step would — the sentinel must
            # catch the loss and the rollback must restore the params
            # (a loss-only poison would let a broken rollback path pass).
            # CXXNET_NAN_LAYER=<name> confines the poison to ONE layer's
            # params — the provenance smoke's ground truth: the
            # diagnostic walk must name exactly that layer
            # (tools/smoke_health.py).
            nan = jnp.float32(float("nan"))
            target = os.environ.get("CXXNET_NAN_LAYER", "")
            if target and target not in self.params:
                raise ValueError(
                    "CXXNET_NAN_LAYER=%r names no param layer (have: %s)"
                    % (target, ", ".join(sorted(self.params))))
            if target:
                p = dict(self.params)
                p[target] = jax.tree_util.tree_map(
                    lambda x: x + nan.astype(x.dtype), p[target])
                self.params = p
            else:
                self.params = jax.tree_util.tree_map(
                    lambda x: x + nan.astype(x.dtype), self.params)
            self._last_loss = float("nan")
        self._step_count += 1
        self.sample_counter += 1
        if self.sample_counter >= self.update_period:
            self.sample_counter = 0
            self.epoch_counter += 1
        # dispatch-side span only: the step RUNS asynchronously; the
        # device-time share is the step-time probe's job (steptime.py)
        TRACER.add_complete("train.step_dispatch", t_dispatch0,
                            time.perf_counter(), cat="train",
                            args={"step": self._step_count})
        if self.eval_train:
            self._drain_pending_metric()
            self._pending_metric = (nodes, batch)

    def _fold_capable(self, batch: DataBatch) -> bool:
        """True when this batch's deferred normalization should ride
        INTO the compiled step (input_fold) instead of running as a
        separate eager normalize dispatch: uint8 payload with norm
        metadata, on the std train path."""
        if not self.input_fold or batch.norm is None:
            return False
        return getattr(batch.data, "dtype", None) == np.uint8

    def _fold_consts(self, norm: dict):
        """Device-side (mean, factor) for the folded step, cached by
        value like ``_norm_fn`` — same precedence and op order as
        ``_device_normalize``."""
        mean = norm.get("mean")
        div = float(norm.get("divideby", 1.0))
        scale = float(norm.get("scale", 1.0))
        key = (None if mean is None
               else np.asarray(mean, np.float32).tobytes(), div, scale)
        if self._fold_cache is None or self._fold_cache[0] != key:
            mean_c = (jnp.asarray(np.asarray(mean, np.float32))
                      if mean is not None else None)
            self._fold_cache = (key, mean_c, jnp.float32(scale / div))
        _, mean_c, factor = self._fold_cache
        return mean_c, factor

    def _fold_args(self, staged: DataBatch):
        """The step's ``data`` argument: the staged array as-is, or the
        ``(uint8, mean, factor)`` tuple the folded step normalizes
        in-trace (_fold_input). jit retraces on the structure switch,
        so folded and unfolded batches can share a Trainer."""
        if not self._fold_capable(staged):
            return staged.data
        mean, factor = self._fold_consts(staged.norm)
        return (staged.data, mean, factor)

    def _device_normalize(self, data, batch: DataBatch):
        """device_normalize pipelines ship uint8 batches (4x smaller H2D)
        and apply mean/divideby HERE, on-device, where the cast+subtract
        is a sub-millisecond bandwidth op instead of a host pass. The
        normalization constants are cached device-side from the first
        batch's metadata."""
        if batch.norm is None:
            return data
        mean = batch.norm.get("mean")
        div = float(batch.norm.get("divideby", 1.0))
        scale = float(batch.norm.get("scale", 1.0))
        # cache keyed by the norm VALUES: train and eval iterators may
        # carry different means (or a mean image that appears later)
        key = (None if mean is None
               else np.asarray(mean, np.float32).tobytes(), div, scale)
        if self._norm_fn is None or self._norm_fn[0] != key:
            mean_c = (jnp.asarray(np.asarray(mean, np.float32))
                      if mean is not None else None)
            factor = np.float32(scale / div)

            @jax.jit
            def norm(x):
                x = x.astype(jnp.float32)
                if mean_c is not None:
                    x = x - mean_c
                if factor != 1.0:
                    x = x * factor
                return x
            self._norm_fn = (key, norm)
        return self._norm_fn[1](data)

    def _mask(self, batch: DataBatch):
        # the all-ones mask (every batch except an epoch's padded tail) is
        # cached device-side per batch size — no per-step H2D transfer
        if not batch.num_batch_padd:
            if self._mask_cache is None \
                    or self._mask_cache[0] != batch.batch_size:
                ones = np.ones((batch.batch_size,), np.float32)
                self._mask_cache = (batch.batch_size,
                                    self.mesh.shard_batch(ones))
            return self._mask_cache[1]
        mask = np.ones((batch.batch_size,), np.float32)
        mask[batch.batch_size - batch.num_batch_padd:] = 0.0
        return self.mesh.shard_batch(mask)

    def _local_rows(self, arr) -> Tuple[np.ndarray, np.ndarray]:
        """Host copy of the batch rows this process can address, plus their
        global row indices. Single-process: all rows. Multi-host: only the
        local shard rows — each process scores its shard and the (sum,cnt)
        accumulators are all-reduced (reference metric.h:60-68 semantics)."""
        if jax.process_count() == 1:
            x = np.asarray(arr)
            return x.reshape(x.shape[0], -1), np.arange(x.shape[0])
        # a node sharded beyond the batch axis (e.g. TP column shards) must
        # be resharded to batch-only first, or the start-keyed dedupe below
        # would drop columns; this device_put runs symmetrically on every
        # rank, so the collective is well-formed
        if any(tuple(sh.data.shape[1:]) != tuple(arr.shape[1:])
               for sh in arr.addressable_shards):
            arr = jax.device_put(arr, self.mesh.batch_sharding(arr.ndim))
        seen: Dict[int, np.ndarray] = {}
        for sh in arr.addressable_shards:
            sl = sh.index[0] if sh.index else slice(None)
            start = sl.start or 0
            if start not in seen:     # replicated arrays: dedupe copies
                seen[start] = np.asarray(sh.data)
        starts = sorted(seen)
        rows = np.concatenate(
            [seen[s].reshape(seen[s].shape[0], -1) for s in starts])
        idx = np.concatenate(
            [np.arange(s, s + seen[s].shape[0]) for s in starts])
        return rows, idx

    def _add_metric(self, mset: MetricSet, nodes: Dict[str, jax.Array],
                    batch: DataBatch) -> None:
        n_real = batch.batch_size - batch.num_batch_padd
        if n_real <= 0:
            return
        label = np.asarray(batch.label if batch.host_label is None
                           else batch.host_label)
        node_vals = {}
        node_labels = {}
        reduced = set()
        for key, arr in nodes.items():
            if key in (_MOE, _DSA):
                continue
            rows, idx = self._local_rows(arr)
            keep = idx < n_real          # drop tail padding rows
            if key.startswith(_REDUCED):
                key = key[len(_REDUCED):]
                reduced.add(None if key == _TOP else key)
            name = None if key == _TOP else key
            node_vals[name] = rows[keep]
            node_labels[name] = label[idx[keep]]
        slices = {name: self.graph.label_slice(name)
                  for name in self.graph.label_name_map}
        mset.add_eval(node_vals, node_labels, slices, reduced)

    # -- evaluation / inference -------------------------------------------
    def _make_eval_step(self, extract: Tuple[str, ...] = ()):
        net = self.net
        needed = sorted(set(self._needed_nodes()) | set(extract))
        capture = bool(needed)

        def step(params, net_state, data, extra):
            res = net.apply(params, net_state, data, extra_data=extra,
                            train=False, capture_nodes=capture)
            return _collect_nodes(res, needed)

        return jax.jit(step)

    def _make_sp_eval_step(self, extract: Tuple[str, ...] = ()):
        """Sequence-parallel inference: partial-manual shard_map over
        ('data','seq') ('model' stays automatic for tp/ep), ring attention
        inside. Captures metric-bound and extracted nodes — every node of
        an sp-safe graph is (b, s, 1, n) with the sequence on axis 1, so
        one out-spec covers them all."""
        from jax.sharding import PartitionSpec as P
        net = self.net
        seq_axis, data_axis = self.mesh.seq_axis, self.mesh.data_axis
        needed = sorted(set(self._needed_nodes()) | set(extract))
        capture = bool(needed)

        def step(params, net_state, data):
            res = net.apply(params, net_state, data, train=False,
                            seq_axis=seq_axis, data_axis=data_axis,
                            capture_nodes=capture)
            return _collect_nodes(res, needed)

        node_spec = P(data_axis, seq_axis, None, None)
        wrapped = jax.shard_map(
            step, mesh=self.mesh.mesh,
            in_specs=(P(), P(), P(data_axis, None, None, seq_axis)),
            out_specs={k: node_spec for k in [_TOP] + needed},
            axis_names={data_axis, seq_axis})
        return jax.jit(wrapped)

    def _eval_nodes(self, batch: DataBatch,
                    extract: Tuple[str, ...] = ()) -> Dict[str, jax.Array]:
        if self._pp > 1:
            # the pp body closes over the probe shapes, so a changed batch
            # shape must rebuild rather than silently reuse a stale pipeline
            pp_key = ("pp", np.shape(batch.data), tuple(extract))
            if self._eval_step_fn is None or self._eval_step_fn[0] != pp_key:
                self._eval_step_fn = (
                    pp_key, self._make_pp_eval_step(np.shape(batch.data),
                                                    extract))
            data = (self._shard_seq_batch(batch.data) if self._sp > 1
                    else self.mesh.shard_batch(batch.data))
            data = self._device_normalize(data, batch)
            return self._eval_step_fn[1](self.params, self.net_state, data)
        if self._sp > 1:
            key = ("sp", tuple(extract))
            if self._eval_step_fn is None or self._eval_step_fn[0] != key:
                self._eval_step_fn = (key, self._make_sp_eval_step(
                    tuple(extract)))
            data = self._device_normalize(self._shard_seq_batch(batch.data),
                                          batch)
            return self._eval_step_fn[1](self.params, self.net_state, data)
        key = tuple(extract)
        if self._eval_step_fn is None or self._eval_step_fn[0] != key:
            self._eval_step_fn = (key, self._make_eval_step(extract))
        data = self._device_normalize(self.mesh.shard_batch(batch.data),
                                      batch)
        extra = tuple(self.mesh.shard_batch(e) for e in batch.extra_data)
        return self._eval_step_fn[1](self.params, self.net_state, data, extra)

    def evaluate(self, data_iter, name: str) -> str:
        """Run all metrics over an iterator; returns the reference's round
        log fragment ``\\tname-metric:value`` (nnet_impl-inl.hpp:241-276).
        In multi-host runs each process evaluates its own shard and the
        (sum, cnt) accumulators are all-reduced, like the reference's rabit
        allreduce inside Metric::Get (metric.h:60-68)."""
        from .parallel import allreduce_metric_pairs
        self.metric.clear()
        # prefetch: batch N+1's H2D overlaps batch N's host-side metric
        # accumulation (_eval_nodes is a no-op re-stage for staged batches)
        with TRACER.span("train.eval", cat="train", args={"set": name}):
            for batch in self.prefetch_device(data_iter, for_eval=True):
                nodes = self._eval_nodes(batch)
                self._add_metric(self.metric, nodes, batch)
        if jax.process_count() > 1:
            self.metric.set_pairs(allreduce_metric_pairs(self.metric.pairs()))
        out = ""
        for mname, val in self.metric.get(name):
            out += "\t%s:%f" % (mname, val)
        return out

    def _drain_pending_metric(self) -> None:
        """Fold the previous step's banked metric nodes into the train
        metric: a host fetch of device values, so it waits for that
        step — ``train.metric_drain``, whichever caller (``update``,
        the chained update, ``train_metric_report``) gets here.
        ``last_drain_s`` keeps the time, for the loop to hand the
        step-time probe apart from the enqueue; every update call
        starts it at zero."""
        if self._pending_metric is None:
            return
        t0 = time.perf_counter()
        nodes, batch = self._pending_metric
        self._pending_metric = None
        if isinstance(batch, list):
            # chain-banked nodes: (k, rows, ...) stacked per step
            for i, b in enumerate(batch):
                self._add_metric(self.train_metric,
                                 {key: v[i] for key, v in nodes.items()}, b)
        else:
            self._add_metric(self.train_metric, nodes, batch)
            if _MOE in nodes:
                self._count_moe(nodes[_MOE])
            if _DSA in nodes:
                self._count_dsa(nodes[_DSA])
        t1 = time.perf_counter()
        self.last_drain_s = t1 - t0
        TRACER.add_complete("train.metric_drain", t0, t1, cat="train")

    def _count_moe(self, stats) -> None:
        """One drained step's no-drop moe ``stats`` (layers/
        moe.MOE_STATS, by layer: ``router = sigmoid`` and ``router =
        softmax_nodrop`` alike) into the telemetry registry: the three
        pair counters summed over the layers and the steps counted (a
        mean's two halves), the gauges by layer — the rung of its buffer
        ladder the layer took among them — the steps on which some
        layer took its ladder's last rung, and the pairs held at this
        step alone (what a reader of a trace's last steps wants while
        the routing drifts)."""
        from .layers.moe import MOE_STATS, buffer_ladder
        from .telemetry.registry import get_registry
        reg = get_registry()
        stats = jax.device_get(stats)
        total = np.zeros(3)
        full = False
        for layer in self.net.layers:
            if layer.name not in stats:
                continue
            v = dict(zip(MOE_STATS, np.asarray(stats[layer.name],
                                               np.float64)))
            pairs = [v["pairs_held"], v["pairs_elsewhere"],
                     v["pairs_dropped"]]
            total += pairs
            full |= v["buffer_rows"] >= buffer_ladder(
                int(sum(pairs)) // layer.topk, layer.topk,
                layer.expert_held, layer.num_expert)[-1]
            for g in MOE_STATS[3:]:
                reg.gauge("cxxnet_moe_" + g, "no-drop moe: " + g
                          + " at the last drained step",
                          labels=("layer",)).labels(layer.name).set(v[g])
        reg.counter("cxxnet_moe_full_buffer_steps_total",
                    "drained steps on which some no-drop moe layer "
                    "took the last rung of its buffer ladder").inc(
                        float(full))
        for kind, n in zip(("held", "elsewhere", "dropped"), total):
            reg.counter(f"cxxnet_moe_pairs_{kind}_total",
                        "no-drop moe: (position, expert) pairs "
                        f"{kind}, summed over layers and drained "
                        "steps").inc(float(n))
        reg.counter("cxxnet_moe_steps_total",
                    "train steps whose moe stats were drained").inc()
        reg.gauge("cxxnet_moe_pairs_held_last_step",
                  "no-drop moe: pairs held at the last drained "
                  "step, summed over layers").set(float(total[0]))

    def _count_dsa(self, stats) -> None:
        """One drained step's sparse-attention ``dsa_stats`` (layers/
        seq.DSA_STATS, by layer) into the telemetry registry as gauges of
        the last drained step: the pairs the selection kept, the
        indexer's loss, and — under the names the other attention kinds
        publish as their nets are built — the score tiles a head's
        kernels executed and the square's, where a tile without a
        selected pair shows as one not executed."""
        from .layers.seq import DSA_STATS
        from .telemetry.registry import get_registry
        reg = get_registry()
        for layer, vec in jax.device_get(stats).items():
            v = dict(zip(DSA_STATS, np.asarray(vec, np.float64)))
            for name, key, text in (
                    ("cxxnet_dsa_selected_pairs", "selected_pairs",
                     "sparse attention: (query, key) pairs the indexer's "
                     "selection kept at the last drained step"),
                    ("cxxnet_dsa_index_loss", "index_loss",
                     "sparse attention: the indexer's loss L_I at the "
                     "last drained step"),
                    ("cxxnet_attn_tiles_executed", "tiles_executed",
                     "score tiles a head of the flash kernel's forward "
                     "executes, at the layer's blocks"),
                    ("cxxnet_attn_tiles_total", "tiles_total",
                     "score tiles of a head's whole square, at the "
                     "layer's blocks")):
                reg.gauge(name, text, labels=("layer",)).labels(layer).set(
                    v[key])
        reg.counter("cxxnet_dsa_steps_total",
                    "train steps whose sparse-attention stats were "
                    "drained").inc()

    def train_metric_report(self, name: str = "train") -> str:
        self._drain_pending_metric()
        if jax.process_count() > 1:   # same global reduction as evaluate()
            from .parallel import allreduce_metric_pairs
            self.train_metric.set_pairs(
                allreduce_metric_pairs(self.train_metric.pairs()))
        out = ""
        for mname, val in self.train_metric.get(name):
            out += "\t%s:%f" % (mname, val)
        self.train_metric.clear()
        return out

    def predict(self, batch: DataBatch) -> np.ndarray:
        """Class predictions (argmax of top node; raw value when the top node
        has one column) — reference Predict + TransformPred
        (nnet_impl-inl.hpp:203-216,317-330)."""
        nodes = self._eval_nodes(batch)
        out = np.asarray(nodes[_TOP])
        out2d = out.reshape(out.shape[0], -1)
        n_real = batch.batch_size - batch.num_batch_padd
        if out2d.shape[1] != 1:
            return np.argmax(out2d[:n_real], axis=1).astype(np.float32)
        return out2d[:n_real, 0]

    def predict_raw(self, batch: DataBatch) -> np.ndarray:
        nodes = self._eval_nodes(batch)
        out = np.asarray(nodes[_TOP])
        n_real = batch.batch_size - batch.num_batch_padd
        return out.reshape(out.shape[0], -1)[:n_real]

    def node_shape(self, node_name: str) -> Tuple[int, int, int]:
        """Per-instance (c, y, x) shape of a named node ('top' = the final
        node) — the extract task's .meta sidecar needs it (the reference
        records pred[0].shape_, cxxnet_main.cpp:402,418)."""
        if node_name in ("top", "top[-1]"):
            return tuple(self.net.out_shape())
        idx = self.graph.node_names.index(node_name)
        return tuple(self.net.node_shapes[idx])

    def extract_feature(self, batch: DataBatch, node_name: str) -> np.ndarray:
        """Extract an intermediate node's value by name (reference
        ExtractFeature, nnet_impl-inl.hpp; 'top' = last node)."""
        if node_name in ("top", "top[-1]"):
            nodes = self._eval_nodes(batch)
            arr = np.asarray(nodes[_TOP])
        else:
            nodes = self._eval_nodes(batch, extract=(node_name,))
            arr = np.asarray(nodes[node_name])
        n_real = batch.batch_size - batch.num_batch_padd
        return arr.reshape(arr.shape[0], -1)[:n_real]

    @property
    def last_loss(self) -> float:
        return float(self._last_loss) if self._last_loss is not None else float("nan")

    @property
    def last_loss_handle(self):
        """The last dispatched step's loss as a DEVICE value (or None) —
        a ready-future for telemetry probes that must choose when to
        sync, unlike :attr:`last_loss` which blocks immediately."""
        return self._last_loss

    @property
    def last_health_handle(self):
        """The last dispatched step's model-health pytree as DEVICE
        values (or None when health is off / the dispatch family does
        not carry it) — same deferred-sync contract as
        :attr:`last_loss_handle`: the HealthProbe decides when to pay
        the host sync (telemetry/modelhealth.py)."""
        return self._last_health

    def params_finite(self) -> bool:
        """Device-side finiteness probe over the param masters (one tiny
        fused reduction). Guards checkpoint writes: a poisoned step whose
        LOSS was still finite (the apply NaN'd the params after the loss
        was computed) must not be persisted — the archive would pass
        integrity verification and every rollback would restore NaN."""
        if self._params_finite_fn is None:
            def probe(params):
                ok = jnp.bool_(True)
                for leaf in jax.tree_util.tree_leaves(params):
                    ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
                return ok
            self._params_finite_fn = jax.jit(probe)
        return bool(self._params_finite_fn(self.params))

    # -- introspection -----------------------------------------------------
    def _describe_step(self, step, args) -> None:
        """Remember the step about to run by its arguments' shapes,
        dtypes and shardings, and register (weakly) how to lower it
        with telemetry.profiler — so that a reader of a profiler dump
        can ask for the compiled text (``step_hlo_text``) without
        holding the trainer. Nothing is lowered or compiled here."""
        from .telemetry import profiler

        def abstract(x):
            if isinstance(x, jax.Array):
                # an uncommitted array (a schedule scalar) follows the
                # others' devices, as in the call itself
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype, weak_type=x.weak_type,
                    sharding=x.sharding if x.committed else None)
            return x
        self._described_step = step
        self._described_args = jax.tree_util.tree_map(abstract, args)
        profiler.register_step(self._lower_described_step)

    def _lower_described_step(self):
        return self._described_step.lower(*self._described_args)

    def _train_step_call(self, batch: DataBatch):
        """(jitted train step, the arguments ``update()`` would hand it
        for ``batch``): what :meth:`lower_train_step` lowers and the
        selection tests trace."""
        assert self.params is not None, "call init_model() first"
        step = self._get_train_step(True, batch)
        mask = self._mask(batch)
        rng = self._rng_key_at(0)       # update()'s: the SAME program
        accum_in = self.accum if self.update_period > 1 else {}
        head = (self.params, self.opt_state, self.net_state, accum_in)
        tail = (rng, self._sched_scalars())
        if self._pp > 1:
            data, label = self.mesh.shard_batch(batch.data, batch.label)
            return step, head + (data, label, mask) + tail
        if self._sp > 1:
            data, label = self._shard_seq_batch(batch.data, batch.label)
            return step, head + (data, label, mask) + tail
        data, label = self.mesh.shard_batch(batch.data, batch.label)
        if self._fold_capable(batch):
            # lower the FOLDED step (uint8 in, normalize in-trace) so
            # the input_fold bytes saving is visible in
            # hbm_bytes_per_step, not hidden outside the step
            mean, factor = self._fold_consts(batch.norm)
            data = (data, mean, factor)
        extra = tuple(self.mesh.shard_batch(e) for e in batch.extra_data)
        return step, head + (data, label, mask, extra) + tail

    def lower_train_step(self, batch: DataBatch):
        """The jitted train step lowered for ``batch`` (a
        ``jax.stages.Lowered``): its text shows which kernels the trace
        selected, and compiling it gives the executable's cost and
        memory analysis — nothing runs."""
        step, args = self._train_step_call(batch)
        return step.lower(*args)

    def step_cost_analysis(self, batch: DataBatch) -> Dict[str, float]:
        """XLA cost analysis of the jitted train step: FLOPs and bytes
        accessed per step, from the compiled executable. Grounds the bench's
        MFU number the way the reference grounds health in GPU utilization
        (reference doc/debug_perf.md:3-5 'normally above 95%')."""
        cost = self.lower_train_step(batch).compile().cost_analysis()
        return {"flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
