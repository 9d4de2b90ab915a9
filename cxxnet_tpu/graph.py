"""Net-config graph compiler: config pairs -> static layer DAG.

TPU-native re-design of the reference NetConfig
(/root/reference/src/nnet/nnet_config.h:26-415). The reference compiles the
order-sensitive ``netconfig=start .. end`` section into a list of LayerInfo
(integer node indices + layer type + per-layer config) that a per-GPU
NeuralNet then executes imperatively with hand-written Backprop. Here the
same grammar compiles into a declarative :class:`NetGraph` that
``cxxnet_tpu.model`` turns into a pure jittable forward function (JAX autodiff
replaces Backprop; XLA replaces the per-device executor).

Grammar supported (nnet_config.h:308-365):
  * ``layer[0->1] = conv:name``         explicit node indices
  * ``layer[a,b->c] = concat``          multi-input / multi-output node lists
  * ``layer[+1] = relu``                new anonymous node after previous top
  * ``layer[+1:tag] = fullc:name``      new named node ``tag``
  * ``layer[+0] = softmax``             self-loop on previous top (losses etc.)
  * ``layer[...] = share[tag]``         weight sharing with primary layer ``tag``
  * ``layer[...] = pairtest-A-B``       side-by-side test composite
  * params after a layer line attach to that layer until the next layer line
  * ``label_vec[a,b) = name``           named label slices (multi-label)
  * ``extra_data_num`` / ``extra_data_shape[i]`` extra input nodes ``in_1..``
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .config import ConfigPairs, ConfigError, Policy, parse_policy

# Layer-type names accepted by the reference factory (layer.h:323-365).
KNOWN_LAYER_TYPES = {
    "fullc", "fixconn", "bias", "softmax", "relu", "sigmoid", "tanh",
    "softplus", "flatten", "dropout", "conv", "relu_max_pooling",
    "max_pooling", "sum_pooling", "avg_pooling", "lrn", "concat", "xelu",
    "maxout", "split", "insanity", "rrelu", "insanity_max_pooling",
    "lp_loss", "l2_loss", "multi_logistic", "ch_concat", "prelu",
    "batch_norm", "batch_norm_no_ma",
    # sequence/transformer extensions (no reference analog; SURVEY §5
    # long-context is N/A there — first-class here)
    "embed", "layernorm", "mha", "ffn", "seqfc", "add", "lmloss", "moe",
    "posembed", "rmsnorm", "mla", "gqa", "dsa", "shortconv", "label_ids",
    # user-plugin layers (the reference's Caffe-adapter plugin spirit,
    # src/plugin/caffe_adapter-inl.hpp: embed foreign layer code in the
    # graph — here a user Python/JAX Layer subclass)
    "plugin",
}


@dataclass
class LayerSpec:
    """One connection in the DAG (reference LayerInfo, nnet_config.h:36-96)."""
    type: str                      # canonical layer type name
    name: str                      # layer name (auto-generated if anonymous)
    nindex_in: List[int]
    nindex_out: List[int]
    cfg: ConfigPairs = field(default_factory=list)
    # weight sharing: index of the primary layer whose params this reuses
    primary_layer_index: Optional[int] = None
    # pairtest composite: (master_type, slave_type)
    pairtest: Optional[Tuple[str, str]] = None

    @property
    def is_shared(self) -> bool:
        return self.primary_layer_index is not None

    def structure_signature(self) -> tuple:
        """Structural identity used for checkpoint-compat checks
        (reference LayerInfo::operator==, nnet_config.h:69-82)."""
        return (self.type, tuple(self.nindex_in), tuple(self.nindex_out),
                self.primary_layer_index)


_LAYER_PLUS = re.compile(r"^layer\[\+(\d+)(?::([^\]]+))?\]$")
_LAYER_ARROW = re.compile(r"^layer\[([^\]]+)->([^\]]+)\]$")
_LABEL_VEC = re.compile(r"^label_vec\[(\d+),(\d+)\)$")
_EXTRA_SHAPE = re.compile(r"^extra_data_shape\[(\d+)\]$")


class NetGraph:
    """Parsed network structure plus global (non-layer) config."""

    def __init__(self) -> None:
        self.node_names: List[str] = ["in"]
        self.node_name_map: Dict[str, int] = {"in": 0, "0": 0}
        self.layers: List[LayerSpec] = []
        self.layer_name_map: Dict[str, int] = {}
        self.defcfg: ConfigPairs = []          # global (non-layer) settings
        self.input_shape: Optional[Tuple[int, int, int]] = None  # (c, y, x)
        self.extra_data_num: int = 0
        self.extra_shapes: List[Tuple[int, int, int]] = []
        # label slicing: list of (begin, end), name -> slice index
        self.label_range: List[Tuple[int, int]] = [(0, 1)]
        self.label_name_map: Dict[str, int] = {"label": 0}
        self._label_default = True
        self.updater_type: str = "sgd"
        self.sync_type: str = "local"

    # -- node helpers ------------------------------------------------------
    def _node_index(self, name: str, alloc_unknown: bool) -> int:
        if name in self.node_name_map:
            return self.node_name_map[name]
        if not alloc_unknown:
            raise ConfigError(
                f"undefined node name {name!r}: input of a layer must be the "
                f"output of an earlier layer")
        idx = len(self.node_names)
        self.node_names.append(name)
        self.node_name_map[name] = idx
        return idx

    @property
    def num_nodes(self) -> int:
        return len(self.node_names)

    def node_index(self, name: str) -> int:
        if name not in self.node_name_map:
            raise ConfigError(f"unknown node name {name!r}")
        return self.node_name_map[name]

    def layer_index(self, name: str) -> int:
        if name not in self.layer_name_map:
            raise ConfigError(f"unknown layer name {name!r}")
        return self.layer_name_map[name]

    # -- label helpers -----------------------------------------------------
    def label_width(self) -> int:
        return max(e for _, e in self.label_range)

    def label_slice(self, name: str) -> Tuple[int, int]:
        return self.label_range[self.label_name_map[name]]

    # -- structure ---------------------------------------------------------
    def structure_signature(self) -> tuple:
        return tuple(l.structure_signature() for l in self.layers)


def _parse_layer_type(val: str, graph: NetGraph, cfg_layer_index: int) -> LayerSpec:
    """Parse the value side ``type[:name]`` of a layer line."""
    if ":" in val:
        ltype, lname = val.split(":", 1)
    else:
        ltype, lname = val, ""
    spec = LayerSpec(type=ltype, name=lname, nindex_in=[], nindex_out=[])
    if ltype.startswith("share"):
        m = re.match(r"^share\[([^\]]+)\]$", ltype)
        if not m:
            raise ConfigError(
                "shared layer must specify tag of layer to share with, "
                "e.g. layer[..] = share[fc1]")
        tag = m.group(1)
        if tag not in graph.layer_name_map:
            raise ConfigError(f"shared layer tag {tag!r} is not defined before")
        spec.type = "share"
        spec.primary_layer_index = graph.layer_name_map[tag]
        if lname:
            if lname in graph.layer_name_map and \
                    graph.layer_name_map[lname] != cfg_layer_index:
                raise ConfigError(f"duplicate layer name {lname!r}")
            graph.layer_name_map[lname] = cfg_layer_index
        return spec
    if ltype.startswith("pairtest-"):
        m = re.match(r"^pairtest-([^-]+)-([^-:]+)$", ltype)
        if not m:
            raise ConfigError(f"invalid pairtest layer type {ltype!r}")
        master, slave = m.group(1), m.group(2)
        for t in (master, slave):
            if t not in KNOWN_LAYER_TYPES:
                raise ConfigError(f"unknown layer type in pairtest: {t!r}")
        spec.type = "pairtest"
        spec.pairtest = (master, slave)
    elif ltype not in KNOWN_LAYER_TYPES:
        raise ConfigError(f"unknown layer type: {ltype!r}")
    if lname:
        if lname in graph.layer_name_map and \
                graph.layer_name_map[lname] != cfg_layer_index:
            raise ConfigError(f"duplicate layer name {lname!r}")
        graph.layer_name_map[lname] = cfg_layer_index
    return spec


def build_graph(cfg: ConfigPairs) -> NetGraph:
    """Compile ordered config pairs into a NetGraph.

    Mirrors NetConfig::Configure (nnet_config.h:213-294): order-sensitive modes
    (netcfg_mode 0/1/2), params after a layer line attach to that layer,
    everything else lands in defcfg.
    """
    graph = NetGraph()
    netcfg_mode = 0
    cfg_top_node = 0
    for name, val in cfg:
        if name == "extra_data_num":
            num = int(val)
            for i in range(num):
                nm = f"in_{i + 1}"
                if nm not in graph.node_name_map:
                    graph.node_name_map[nm] = len(graph.node_names)
                    graph.node_names.append(nm)
            graph.extra_data_num = num
            continue
        m = _EXTRA_SHAPE.match(name)
        if m:
            dims = tuple(int(x) for x in val.split(","))
            if len(dims) != 3:
                raise ConfigError(f"extra data shape config incorrect: {val!r}")
            graph.extra_shapes.append(dims)
            continue
        if name == "input_shape":
            dims = tuple(int(x) for x in val.split(","))
            if len(dims) != 3:
                raise ConfigError(
                    "input_shape must be three integers c,y,x e.g. 1,1,784")
            graph.input_shape = dims
            # falls through into defcfg too (harmless, mirrors reference)
        if netcfg_mode != 2:
            if name == "updater":
                graph.updater_type = val
            elif name == "sync":
                graph.sync_type = val
            mlv = _LABEL_VEC.match(name)
            if mlv:
                if graph._label_default:
                    graph.label_range = []
                    graph.label_name_map = {}
                    graph._label_default = False
                graph.label_range.append((int(mlv.group(1)), int(mlv.group(2))))
                graph.label_name_map[val] = len(graph.label_range) - 1
                continue
        if name == "netconfig" and val == "start":
            netcfg_mode = 1
            continue
        if name == "netconfig" and val == "end":
            netcfg_mode = 0
            continue
        if name.startswith("layer["):
            cfg_layer_index = len(graph.layers)
            spec = _parse_layer_type(val, graph, cfg_layer_index)
            mp = _LAYER_PLUS.match(name)
            ma = _LAYER_ARROW.match(name)
            if mp:
                inc = int(mp.group(1))
                tag = mp.group(2)
                if cfg_top_node < 0:
                    raise ConfigError(
                        "layer[+k] used after a layer with multiple outputs; "
                        "use layer[in->out] instead")
                spec.nindex_in = [cfg_top_node]
                if tag is not None and inc == 1:
                    spec.nindex_out = [graph._node_index(tag, True)]
                elif inc == 0:
                    spec.nindex_out = [cfg_top_node]
                else:
                    anon = f"!node-after-{cfg_top_node}"
                    spec.nindex_out = [graph._node_index(anon, True)]
            elif ma:
                for nm in ma.group(1).split(","):
                    spec.nindex_in.append(graph._node_index(nm, False))
                for nm in ma.group(2).split(","):
                    spec.nindex_out.append(graph._node_index(nm, True))
            else:
                raise ConfigError(f"invalid layer format {name!r}")
            if not spec.name:
                spec.name = f"{spec.type}_{cfg_layer_index}"
                # auto-names must not collide with user names
                while spec.name in graph.layer_name_map:
                    spec.name = "_" + spec.name
                graph.layer_name_map[spec.name] = cfg_layer_index
            graph.layers.append(spec)
            netcfg_mode = 2
            cfg_top_node = spec.nindex_out[0] if len(spec.nindex_out) == 1 else -1
            continue
        if netcfg_mode == 2:
            if graph.layers[-1].is_shared:
                raise ConfigError(
                    "do not set parameters on a shared layer; set them on the "
                    "primary layer")
            graph.layers[-1].cfg.append((name, val))
        else:
            graph.defcfg.append((name, val))
    if graph.extra_data_num and \
            len(graph.extra_shapes) != graph.extra_data_num:
        raise ConfigError("extra_data_shape count does not match extra_data_num")
    return graph


#: producers that apply a following relu themselves: batch_norm after
#: the normalize, conv/fullc after the bias
ACT_FUSABLE_PRODUCERS = ("batch_norm", "batch_norm_no_ma", "conv", "fullc")


def act_fusion_plan(graph: NetGraph):
    """Static activation-fold plan: find producer -> relu edges where
    the relu can be applied by the producer.

    Returns ``(fuse_act, folded)``: ``fuse_act`` maps a producer layer
    index to the activation name it must apply ("relu"), ``folded`` is
    the set of relu layer indices that become pass-throughs in
    ``Network.apply``. The fold is VALUE-preserving for every node a
    later layer reads:

    * an in-place relu (``layer[+0]``) rewrites the producer's node, so
      all later consumers already read the post-activation value — safe
      regardless of fan-out;
    * a relu writing a new node is folded only when it is the SOLE
      consumer of the producer's output (otherwise some layer reads the
      pre-activation value, which the fold would destroy).

    Folded producers apply the activation themselves (see the
    layers), so the plan is computed once per Network.
    """
    consumers: Dict[int, List[int]] = {}
    for li, spec in enumerate(graph.layers):
        for ni in set(spec.nindex_in):
            consumers.setdefault(ni, []).append(li)
    fuse_act: Dict[int, str] = {}
    folded: set = set()
    for li, spec in enumerate(graph.layers):
        if spec.is_shared or spec.type not in ACT_FUSABLE_PRODUCERS:
            continue
        if len(spec.nindex_out) != 1:
            continue
        out = spec.nindex_out[0]
        later = sorted(c for c in consumers.get(out, []) if c > li)
        if not later:
            continue
        ri = later[0]
        rs = graph.layers[ri]
        if (rs.type != "relu" or rs.is_shared or rs.nindex_in != [out]
                or len(rs.nindex_out) != 1):
            continue
        if rs.nindex_out[0] != out and len(later) > 1:
            continue     # another layer reads the pre-activation node
        fuse_act[li] = "relu"
        folded.add(ri)
    return fuse_act, folded


def stem_pad_plan(graph: NetGraph, pad_to: int = 4) -> Dict[int, int]:
    """Stem channel-padding plan (second kernel wave, doc/ibn_perf.md):
    conv layers reading the RAW graph input with fewer than ``pad_to``
    channels get their input (and the matching weight dim) zero-padded
    to ``pad_to`` at apply time. RGB stems leave 125 of the MXU's 128
    systolic rows idle; padding 3 -> 4 makes the channel dim (and the
    space-to-depth fold's s*s*cin product) a power-of-two lane/sublane
    multiple. Value-exact: zero input channels times zero weight taps
    contribute nothing, and the traced pad's transpose is a slice, so
    gradients to the canonical-shape weights are unchanged.

    Returns {layer_index: pad_to} — only first-layer convs qualify
    (deeper channel counts are layer-controlled and already large).
    """
    plan: Dict[int, int] = {}
    if graph.input_shape is None or pad_to <= 0:
        return plan
    if graph.input_shape[0] >= pad_to:
        return plan
    for li, spec in enumerate(graph.layers):
        if (spec.type == "conv" and not spec.is_shared
                and spec.nindex_in == [0]):
            plan[li] = pad_to
    return plan


def global_param(cfg: ConfigPairs, name: str, default: str = "") -> str:
    """Last-wins lookup of a global setting (CLI overrides come last)."""
    out = default
    for k, v in cfg:
        if k == name:
            out = v
    return out


def policy_from_config(cfg: ConfigPairs) -> Policy:
    """Resolve the mixed-precision :class:`~cxxnet_tpu.config.Policy`
    from the ``compute_dtype`` global (default float32 — reference
    parity: mshadow real_t, src/global.h)."""
    return parse_policy(global_param(cfg, "compute_dtype", "float32"))


def sharding_from_config(cfg: ConfigPairs):
    """Resolve the rule-driven sharding namespace
    (:func:`~cxxnet_tpu.config.parse_sharding_config`:
    ``partition_rules`` / ``fsdp_axis`` / ``fsdp_min_size``) — the
    graph-level accessor beside :func:`policy_from_config`, so every
    Network/Trainer build validates the namespace exactly once per
    config, typos raising at build time like a bad compute_dtype."""
    from .config import parse_sharding_config
    return parse_sharding_config(cfg)
