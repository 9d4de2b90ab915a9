"""Layer base classes for the TPU-native framework.

Reference analog: ILayer<xpu> (/root/reference/src/layer/layer.h:163-280).
The re-design is functional: a layer is a stateless object holding parsed
hyperparameters; parameters and mutable state (BN running stats, annealing
counters) live in pytrees threaded through a pure ``apply``. JAX autodiff
replaces the reference's hand-written per-layer ``Backprop``.

Array convention: every node is a 4-D NHWC array ``(batch, y, x, c)``.
"Flat" nodes are ``(batch, 1, 1, n)`` with features on the channel axis
(the reference uses NCHW ``(batch, c, y, x)`` with flat features on the x
axis; NHWC is the TPU-native layout so convs tile onto the MXU).
Logical per-node shapes (without batch) are tracked as ``(c, y, x)`` tuples
to match the config dialect ``input_shape = c,y,x``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..config import ConfigPairs
from ..graph import LayerSpec

Shape3 = Tuple[int, int, int]   # (c, y, x)
Params = Dict[str, jax.Array]
State = Dict[str, Any]


def is_flat(shape: Shape3) -> bool:
    return shape[0] == 1 and shape[1] == 1


def to_nhwc(shape: Shape3, batch: int) -> Tuple[int, int, int, int]:
    c, y, x = shape
    if is_flat(shape):
        return (batch, 1, 1, x)
    return (batch, y, x, c)


def flat_size(shape: Shape3) -> int:
    c, y, x = shape
    return c * y * x


@dataclasses.dataclass
class LayerHyper:
    """Shared layer hyperparameters (reference LayerParam, param.h:14-142)."""
    num_hidden: int = 0
    init_sigma: float = 0.01
    init_uniform: float = -1.0
    init_bias: float = 0.0
    num_channel: int = 0
    random_type: int = 0            # 0 gaussian, 1 uniform/xavier, 2 kaiming
    num_group: int = 1
    kernel_height: int = 0
    kernel_width: int = 0
    stride: int = 1
    pad_y: int = 0
    pad_x: int = 0
    no_bias: int = 0
    silent: int = 0
    dtype: Any = jnp.float32

    def set_param(self, name: str, val: str) -> None:
        if name == "init_sigma":
            self.init_sigma = float(val)
        elif name == "init_uniform":
            self.init_uniform = float(val)
        elif name == "init_bias":
            self.init_bias = float(val)
        elif name == "random_type":
            mapping = {"gaussian": 0, "uniform": 1, "xavier": 1, "kaiming": 2}
            if val not in mapping:
                raise ValueError(f"invalid random_type {val!r}")
            self.random_type = mapping[val]
        elif name == "nhidden":
            self.num_hidden = int(val)
        elif name == "nchannel":
            self.num_channel = int(val)
        elif name == "ngroup":
            self.num_group = int(val)
        elif name == "kernel_size":
            self.kernel_height = self.kernel_width = int(val)
        elif name == "kernel_height":
            self.kernel_height = int(val)
        elif name == "kernel_width":
            self.kernel_width = int(val)
        elif name == "stride":
            self.stride = int(val)
        elif name == "pad":
            self.pad_y = self.pad_x = int(val)
        elif name == "pad_y":
            self.pad_y = int(val)
        elif name == "pad_x":
            self.pad_x = int(val)
        elif name == "no_bias":
            self.no_bias = int(val)
        elif name == "silent":
            self.silent = int(val)

    def init_weight(self, key: jax.Array, shape: Sequence[int],
                    in_num: int, out_num: int) -> jax.Array:
        """Weight init matching reference RandInitWeight (param.h:105-131)."""
        if self.random_type == 0:
            return self.init_sigma * jax.random.normal(key, shape, self.dtype)
        if self.random_type == 1:
            a = (3.0 / (in_num + out_num)) ** 0.5
            if self.init_uniform > 0:
                a = self.init_uniform
            return jax.random.uniform(key, shape, self.dtype, -a, a)
        if self.random_type == 2:
            if self.num_hidden > 0:
                sigma = (2.0 / self.num_hidden) ** 0.5
            else:
                sigma = (2.0 / (self.num_channel * self.kernel_width *
                                self.kernel_height)) ** 0.5
            return sigma * jax.random.normal(key, shape, self.dtype)
        raise ValueError(f"unsupported random_type {self.random_type}")


@dataclasses.dataclass
class ApplyCtx:
    """Per-call context threaded into Layer.apply."""
    train: bool
    rng: Optional[jax.Array] = None     # folded per-layer key, stochastic layers
    compute_dtype: Any = jnp.float32
    # bound when the whole step runs under shard_map with the sequence
    # sharded (seq_parallel > 1): attention layers switch to the ring path
    seq_axis: Optional[str] = None
    # bound alongside seq_axis when the batch axis is also manual in the
    # shard_map — layers whose statistics must be global (MoE aux loss)
    # reduce over it too
    data_axis: Optional[str] = None
    # pipeline stages set this with seq_axis: attention uses the gather-kv
    # path (all_gather rendezvous is subgroup-scoped and safe inside a
    # lax.switch branch) instead of the ring (collective_permute's global
    # rendezvous deadlocks when other stages never reach it)
    seq_gather_kv: bool = False
    # bound inside the pipeline-parallel schedule (train only): layers with
    # batch statistics (batch_norm) record raw microbatch moments here
    # instead of updating running state — the schedule accumulates them
    # across microbatches and the trainer merges one exact full-batch EMA
    # update after the ring (see Network.apply_stage)
    stat_sink: Optional[Dict[str, Any]] = None
    # activation folded into this layer by the graph-level plan
    # (graph.act_fusion_plan): "relu" or None. The folded relu layer is
    # a pass-through, so a layer handed one MUST apply it.
    fuse_act: Optional[str] = None
    # stem channel padding (graph.stem_pad_plan): pad this conv's input
    # channels (and the matching weight dim) with zeros up to this count
    # at apply time — value-exact (zero channels x zero taps contribute
    # nothing; the pad/slice pair transposes exactly under autodiff),
    # params/checkpoints keep the canonical shape. None = no pad.
    cin_pad: Optional[int] = None
    # model-health activation sink (telemetry/modelhealth.py): bound by
    # Network.apply when ``health = 1`` — the standard per-layer taps
    # (abs-max, dead-ReLU fraction, BN batch-variance floor) are written
    # by Network.apply itself; a plugin layer may add its OWN fp32
    # scalar stats under its layer name. None = health off (the default
    # path pays one attribute check, nothing more).
    health_sink: Optional[Dict[str, Any]] = None
    # the batch's label slices by name (``label_vec``), (batch, w) each:
    # bound by Network.apply where it was handed the whole label, for the
    # one kind that reads labels as an INPUT (``label_ids``). None
    # otherwise (inference; the sequence-parallel and pipeline steps).
    labels: Optional[Dict[str, Any]] = None


class Layer:
    """Base class: parse hyperparams at construction, pure apply at runtime."""

    # subclasses override
    has_params = False
    has_state = False
    is_loss = False
    # manual tensor parallelism under pipeline stages (Network.
    # tp_manual_plan): tp_follow = True marks a CHANNEL-WISE layer (no
    # cross-channel mixing on the trailing axis) that can consume a
    # channel-sharded activation and emit one — the producing conv/fullc's
    # output all-gather is deferred past it, cutting HBM traffic on the
    # gathered activation. tp_channel_params/state name (C,)-shaped leaves
    # to slice per model shard alongside the activation (BN gamma/beta,
    # prelu slope, running stats).
    tp_follow = False
    tp_channel_params: Tuple[str, ...] = ()
    tp_channel_state: Tuple[str, ...] = ()

    def tp_followable(self, train: bool) -> bool:
        """Whether this layer instance can run channel-sharded in the
        given mode — stochastic layers veto at train time (a same-keyed
        rng draw per shard would decorrelate from the unsharded run)."""
        return self.tp_follow

    def __init__(self, spec: LayerSpec, global_cfg: ConfigPairs):
        self.spec = spec
        self.name = spec.name
        self.hp = LayerHyper()
        for k, v in global_cfg:
            self.hp.set_param(k, v)
            self.set_param(k, v)
        for k, v in spec.cfg:
            self.hp.set_param(k, v)
            self.set_param(k, v)

    # -- hooks -------------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        """Layer-specific config hook (reference ILayer::SetParam)."""

    def infer_shapes(self, in_shapes: List[Shape3]) -> List[Shape3]:
        """Output logical shapes given input logical shapes."""
        raise NotImplementedError

    def init_params(self, key: jax.Array, in_shapes: List[Shape3]) -> Params:
        return {}

    def param_pspecs(self) -> Dict[str, Any]:
        """Tensor-parallel PartitionSpec tuples per param key (missing =
        replicated). Layers with large weights override to shard over the
        mesh 'model' axis — the general form of the reference's
        fullc_gather hybrid parallelism (async_updater-inl.hpp:68-94)."""
        return {}

    def init_state(self, in_shapes: List[Shape3]) -> State:
        return {}

    def apply(self, params: Params, state: State, inputs: List[jax.Array],
              ctx: ApplyCtx) -> Tuple[List[jax.Array], State]:
        raise NotImplementedError

    # -- loss-layer extras -------------------------------------------------
    def loss(self, outputs: List[jax.Array], label: jax.Array,
             mask: jax.Array) -> jax.Array:
        """Scalar loss contribution; only loss layers implement this.

        ``label`` is the (batch, w) slice bound to this layer's target;
        ``mask`` is (batch,) 1/0 marking real (non-padded) rows.
        """
        raise NotImplementedError

    # -- helpers -----------------------------------------------------------
    def check_n(self, in_shapes: List[Shape3], n_in: int, n_out: int) -> None:
        if len(self.spec.nindex_in) != n_in or len(self.spec.nindex_out) != n_out:
            raise ValueError(
                f"{self.spec.type} layer {self.name!r}: needs {n_in} input(s) "
                f"and {n_out} output(s), got {len(self.spec.nindex_in)}->"
                f"{len(self.spec.nindex_out)}")


LAYER_REGISTRY: Dict[str, type] = {}


def register_layer(*names: str):
    def deco(cls):
        for n in names:
            LAYER_REGISTRY[n] = cls
        cls.type_names = names
        return cls
    return deco
