"""Device mesh + sharding context.

TPU-native replacement for the reference's entire distribution stack: the
pthread-per-GPU worker pool (neural_net-inl.hpp:324-658), the mshadow-ps
push/pull parameter server in its three flavors (NONE/local/dist, created at
nnet_impl-inl.hpp:409-423), and rabit allreduce. One ``jax.sharding.Mesh``
with a ``('data',)`` axis (plus an optional ``'model'`` axis for tensor
parallelism of big FC layers — the general form of the reference's
``fullc_gather`` trick, async_updater-inl.hpp:68-94) replaces all of it:
batches are sharded over 'data', params are replicated (or sharded over
'model'), and XLA inserts the gradient all-reduce over ICI where the
reference pushed per-layer gradients to the PS with priority scheduling.

Device spec grammar matches the reference trainer (nnet_impl-inl.hpp:38-67):
``dev = cpu`` / ``gpu`` / ``tpu`` / ``tpu:0-3`` / ``tpu:0,2,5``.
Multi-host: call ``jax.distributed.initialize`` before building the context
(the analog of rabit::Init / ps-lite trackers) — ``jax.devices()`` then spans
all hosts and the same mesh code scales over DCN.
"""

from __future__ import annotations

import dataclasses
import re
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def parse_device_spec(spec: str) -> Optional[List[int]]:
    """Parse ``dev`` config value into a device-index list (None = all/default).

    Mirrors nnet_impl-inl.hpp:38-67: 'gpu:0-3' is an inclusive range
    [0,3] (the reference loops ``for i=a; i<=b``), 'gpu:0,2' an explicit
    list, bare 'gpu'/'cpu'/'tpu' = default (all devices).
    """
    spec = spec.strip()
    m = re.match(r"^[a-z]+$", spec)
    if m:
        return None
    m = re.match(r"^[a-z]+:(\d+)-(\d+)$", spec)
    if m:
        return list(range(int(m.group(1)), int(m.group(2)) + 1))
    m = re.match(r"^[a-z]+:([\d,]+)$", spec)
    if m:
        return [int(x) for x in m.group(1).split(",")]
    raise ValueError(f"cannot parse device spec {spec!r}")


@dataclasses.dataclass
class MeshContext:
    mesh: Mesh
    data_axis: str = "data"
    model_axis: str = "model"
    seq_axis: str = "seq"
    pipe_axis: str = "pipe"

    @property
    def num_devices(self) -> int:
        return self.mesh.size

    @property
    def data_parallel(self) -> int:
        return self.mesh.shape[self.data_axis]

    @property
    def seq_parallel(self) -> int:
        return self.mesh.shape.get(self.seq_axis, 1)

    @property
    def pipeline_parallel(self) -> int:
        return self.mesh.shape.get(self.pipe_axis, 1)

    # -- shardings ---------------------------------------------------------
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self, ndim: int) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.data_axis,
                                          *([None] * (ndim - 1))))

    def shard_batch(self, *arrays):
        """Place host arrays on the mesh, sharded over the data axis."""
        out = []
        for a in arrays:
            if a is None:
                out.append(None)
                continue
            out.append(jax.device_put(a, self.batch_sharding(np.ndim(a))))
        return out if len(out) != 1 else out[0]

    def replicate(self, tree):
        """Place a pytree on the mesh fully replicated (params, opt state)."""
        sh = self.replicated()
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)

    @property
    def model_parallel(self) -> int:
        return self.mesh.shape[self.model_axis]

    def named(self, spec) -> NamedSharding:
        """PartitionSpec(-able) -> NamedSharding on this mesh."""
        if spec is None:
            return self.replicated()
        if not isinstance(spec, P):
            spec = P(*spec)
        return NamedSharding(self.mesh, spec)

    def shard_params(self, tree, pspec_tree):
        """Place a params-like pytree with per-leaf PartitionSpecs.

        ``pspec_tree`` mirrors ``tree`` but may omit subtrees/leaves (missing
        = replicated). This is the TPU-native generalization of the
        reference's fullc_gather model-parallel trick
        (async_updater-inl.hpp:68-94): instead of gathering activations and
        computing dW redundantly, big weights are sharded over the 'model'
        axis and GSPMD inserts the collectives.
        """
        def usable(spec_sub, shape) -> bool:
            """A spec is usable only when every sharded dim divides evenly;
            otherwise fall back to replicated (e.g. nhidden=10 over a
            4-way model axis)."""
            for dim, axis in enumerate(spec_sub):
                if axis is None:
                    continue
                if dim >= len(shape) or shape[dim] % self.mesh.shape[axis]:
                    return False
            return True

        def place(sub, spec_sub):
            if isinstance(sub, dict):
                return {k: place(v, (spec_sub or {}).get(k)
                                 if isinstance(spec_sub, dict) else None)
                        for k, v in sub.items()}
            if spec_sub is not None and not usable(spec_sub, np.shape(sub)):
                spec_sub = None
            return jax.device_put(sub, self.named(spec_sub))
        return place(tree, pspec_tree)

    def gather(self, tree):
        """Bring a (possibly model-sharded) pytree to fully-replicated form
        so host-side fetches (np.asarray for checkpoints / get_weight) work
        in multi-host runs where each process only holds its local shards."""
        sh = self.replicated()
        def g(x):
            if hasattr(x, "sharding") and x.sharding.is_fully_replicated:
                return x
            return jax.device_put(x, sh)
        return jax.tree_util.tree_map(g, tree)


def maybe_distributed_init(cfg) -> bool:
    """Multi-host bring-up (the analog of rabit::Init / the ps-lite tracker
    handshake, reference cxxnet_main.cpp:74-92): when the config carries
    ``dist_coordinator`` (host:port), call jax.distributed.initialize so
    jax.devices() spans every host and the same mesh code scales over DCN.
    Process count/rank come from ``dist_num_proc``/``dist_rank`` or the
    standard cluster env detection. Returns True when initialization ran.

    Config keys: dist_coordinator, dist_num_proc, dist_rank, dist_timeout
    (seconds; bounds the coordinator handshake so a wrong address fails
    with a diagnostic instead of hanging forever — the analog of the
    reference tracker reporting bad ranks).
    """
    global LAST_DIST_INIT
    coord = num = rank = None
    timeout = 300
    for k, v in cfg:
        if k == "dist_coordinator":
            coord = v
        elif k == "dist_num_proc":
            num = int(v)
        elif k == "dist_rank":
            rank = int(v)
        elif k == "dist_timeout":
            timeout = int(v)
    if not coord:
        return False
    try:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=num, process_id=rank,
                                   initialization_timeout=timeout)
    except Exception as e:
        raise RuntimeError(
            f"distributed init failed (coordinator={coord!r}, rank={rank}, "
            f"num_proc={num}, timeout={timeout}s): check dist_coordinator "
            "is reachable from every rank and all ranks were launched") from e
    # recorded for the run ledger's run_start event (the telemetry
    # session is built AFTER multi-host bring-up, so this is a note
    # the ledger picks up rather than an event emitted here)
    LAST_DIST_INIT = {"coordinator": coord, "num_proc": num, "rank": rank}
    return True


# multi-host bring-up details of the last successful
# jax.distributed.initialize in this process (None = single-process run)
LAST_DIST_INIT = None


def allreduce_metric_pairs(pairs):
    """Sum (sum, cnt) metric accumulators across hosts — the TPU-native
    analog of the reference's rabit allreduce inside Metric::Get
    (utils/metric.h:60-68). Identity in single-process runs."""
    if jax.process_count() == 1:
        return pairs
    from jax.experimental import multihost_utils
    arr = np.asarray(pairs, np.float64)          # (n_metrics, 2)
    # allgather moves data through jnp, which would canonicalize float64 to
    # float32 without x64 mode (corrupting counts > 2^24); bit-cast to
    # uint32 for the transport and reassemble host-side.
    bits = np.ascontiguousarray(arr).view(np.uint32)
    gathered = multihost_utils.process_allgather(bits)  # (n_proc, n, 4)
    tot = np.sum(np.asarray(gathered).view(np.float64), axis=0)
    return [(float(s), int(c)) for s, c in tot]


def force_cpu_devices(n: int) -> None:
    """Pin this process to the CPU backend with ``n`` virtual devices
    (the multi-machine worker scripts, the shard smoke, rehearsals of a
    multi-chip path). Must run before the first device query."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n))


def _cpu_pinned() -> bool:
    """Whether this process was held to the CPU backend on purpose
    (``JAX_PLATFORMS=cpu`` or ``jax_platforms`` set to ``cpu``) — how
    the tests and the verify recipe run ``dev = tpu`` configs."""
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


#: the "pin overrode dev" line prints once per process
_PIN_NOTED = False


def devices_for(dev: str) -> List:
    """The devices a ``dev`` config value names. ``cpu`` pins the CPU
    backend (before the first device query). An accelerator platform
    must be the one JAX actually found: where JAX finds no chip it
    quietly hands out the CPU backend, and a ``dev = tpu`` run that
    trains there without a word is the failure this closes. A process
    pinned to the CPU on purpose keeps running such configs, and says
    so once."""
    global _PIN_NOTED
    idx = parse_device_spec(dev)
    want = dev.strip().split(":")[0]
    if want == "cpu":
        jax.config.update("jax_platforms", "cpu")
    all_devs = jax.devices()
    found = all_devs[0].platform
    if want != found and want != "cpu":
        if not _cpu_pinned():
            raise RuntimeError(
                f"dev = {dev} asks for platform {want!r} but JAX found "
                f"only {found!r} devices ({all_devs[0].device_kind}): "
                f"refusing to run a {want} config on the {found} backend. "
                "Fix the accelerator, or say dev = cpu / JAX_PLATFORMS=cpu "
                "if the CPU is what you mean.")
        if not _PIN_NOTED:
            _PIN_NOTED = True
            print(f"dev = {dev} overridden by the JAX_PLATFORMS=cpu pin: "
                  f"running on the {found} backend", flush=True)
    return all_devs if idx is None else [all_devs[i] for i in idx]


def make_mesh_context(dev: str = "tpu",
                      devices: Optional[Sequence] = None,
                      model_parallel: int = 1,
                      seq_parallel: int = 1,
                      pipeline_parallel: int = 1) -> MeshContext:
    """Build the mesh. ``dev`` is the config device spec
    (:func:`devices_for`); ``devices`` overrides explicitly (used by
    tests to build CPU meshes). Axes: ``('data', 'pipe', 'seq',
    'model')`` — pipe/seq/model default to size 1 so pure data-parallel
    code is unaffected."""
    if devices is None:
        devices = devices_for(dev)
    n = len(devices)
    denom = model_parallel * seq_parallel * pipeline_parallel
    if n % denom:
        raise ValueError(
            f"{n} devices not divisible by model_parallel={model_parallel} "
            f"x seq_parallel={seq_parallel} "
            f"x pipeline_parallel={pipeline_parallel}")
    arr = np.asarray(devices).reshape(
        n // denom, pipeline_parallel, seq_parallel, model_parallel)
    mesh = Mesh(arr, ("data", "pipe", "seq", "model"))
    return MeshContext(mesh=mesh)
