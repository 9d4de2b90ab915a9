"""Data iterator protocol and factory.

Reference: IIterator<DataBatch>/DataInst/DataBatch
(/root/reference/src/io/data.h:19-183) and the config-ordered iterator
chain factory (data.cpp:27-94). Batches are host numpy arrays in NHWC (flat
nodes (n,1,1,k)); ``num_batch_padd`` marks trailing padded rows of the final
partial batch so XLA always sees static shapes and metrics/losses mask the
padding (SURVEY §7 "dynamic batch tail").
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Type

import numpy as np

from ..config import ConfigPairs
from ..telemetry.trace import TRACER


@dataclasses.dataclass
class DataBatch:
    data: np.ndarray                      # (batch, y, x, c) or (batch,1,1,n)
    label: np.ndarray                     # (batch, label_width) float32
    num_batch_padd: int = 0               # trailing rows that are padding
    inst_index: Optional[np.ndarray] = None  # (batch,) instance ids
    extra_data: List[np.ndarray] = dataclasses.field(default_factory=list)
    # device_normalize=1 pipelines: data is uint8 and this carries the
    # deferred normalization {"mean": (3,)|(y,x,c)|None, "divideby": f}
    # for the trainer to apply on-device after the (4x smaller) H2D copy
    norm: Optional[dict] = None
    # batches staged on-device (Trainer.stage_batch) keep the host label
    # here: metrics index labels host-side, and in multi-host runs the
    # staged device label spans non-addressable shards
    host_label: Optional[np.ndarray] = None

    @property
    def batch_size(self) -> int:
        return self.data.shape[0]


class DataIter:
    """Iterator protocol (reference IIterator, data.h:19-39)."""

    #: True on SOURCE iterators that honor dist_num_worker /
    #: dist_worker_rank (serve a 1/nworker row slice). Declared on the
    #: implementing class so the data service's shardability check can
    #: never drift from the code: dist_shardable_sources() derives the
    #: allowed set from the registry.
    supports_dist_shard = False
    #: whether ``__iter__`` has handed out its first batch: the making of
    #: that one is set-up, and is recorded as ``setup.input``
    _first_made = False

    def __init__(self, cfg: ConfigPairs):
        self.cfg = cfg
        for k, v in cfg:
            self.set_param(k, v)

    def set_param(self, name: str, val: str) -> None:
        pass

    def init(self) -> None:
        pass

    def before_first(self) -> None:
        raise NotImplementedError

    def next(self) -> Optional[DataBatch]:
        """Return the next batch or None at end of epoch."""
        raise NotImplementedError

    def __iter__(self):
        if self._first_made:
            self.before_first()
        else:
            t0 = time.perf_counter()
            self.before_first()
            b = self.next()
            self._first_made = True
            TRACER.add_complete("setup.input", t0, time.perf_counter(),
                                cat="setup")
            if b is None:
                return
            yield b
        while True:
            b = self.next()
            if b is None:
                return
            yield b


def close_chain(it) -> None:
    """Release an iterator chain's background resources, walking
    ``.base`` links: threadbuffer producers (``close()``) and decode
    thread pools (``_pool``). The teardown for ANY chain — wrappers
    need not each forward close() for an abandoned chain to avoid
    leaking a spinning producer or an 8-thread executor."""
    seen = set()
    while it is not None and id(it) not in seen:
        seen.add(id(it))
        close = getattr(it, "close", None)
        if callable(close):
            try:
                close()
            except Exception:
                pass
        pool = getattr(it, "_pool", None)
        if pool is not None and hasattr(pool, "shutdown"):
            pool.shutdown(wait=False)
        it = getattr(it, "base", None)


def dist_slice(n: int, nworker: int, rank: int) -> slice:
    """Contiguous row range of worker ``rank`` of ``nworker`` over
    ``n`` rows — the imgrec byte-range rule applied to row-indexed
    sources (first ``n % nworker`` workers carry one extra row), so
    the union over ranks is exactly the full dataset."""
    if not 0 <= rank < nworker:
        raise ValueError(f"dist_worker_rank {rank} outside "
                         f"[0, dist_num_worker={nworker})")
    base, extra = divmod(n, nworker)
    start = rank * base + min(rank, extra)
    return slice(start, start + base + (1 if rank < extra else 0))


ITER_REGISTRY: Dict[str, Type[DataIter]] = {}


def register_iter(*names: str):
    def deco(cls):
        for n in names:
            ITER_REGISTRY[n] = cls
        return cls
    return deco


class SkipReadIterator(DataIter):
    """``test_skipread = 1`` (reference iter_batch_proc-inl.hpp:21,47,69):
    serve a cached batch without touching the source — the IO-benchmark
    knob that isolates read/decode cost from everything downstream.
    Bounded deviation from the reference (whose Next() returns the first
    batch FOREVER): the first epoch streams (and counts) real batches;
    every later epoch re-serves the first batch that many times. With
    ``test_io = 1`` over 2+ rounds the driver prints the real-IO rate
    (round 0) and the skipread rate (round 1+); the gap is the read/
    decode cost."""

    def __init__(self, base: DataIter):
        self.base = base
        self._first: Optional[DataBatch] = None
        self._count = 0
        self._known = False
        self._pos = 0
        super().__init__([])

    def before_first(self):
        self._pos = 0
        if not self._known:
            # an interrupted first pass must not leave a partial count
            # behind — only a COMPLETE first epoch defines the cadence
            self._count = 0
            self._first = None
            self.base.before_first()

    def next(self):
        if not self._known:
            b = self.base.next()
            if b is None:
                self._known = True
                # end-of-epoch stays None until before_first re-arms
                # (chained-iterator protocol: MNIST/CSV behave the same)
                self._pos = self._count
                return None
            if self._first is None:
                self._first = b
            self._count += 1
            return b
        if self._first is None or self._pos >= self._count:
            return None
        self._pos += 1
        return self._first


def dist_shardable_sources() -> list:
    """Source iterator types declaring ``supports_dist_shard``."""
    from . import proc, iter_imgrec, iter_img  # noqa: F401  (populate registry)
    return sorted(n for n, c in ITER_REGISTRY.items()
                  if c.supports_dist_shard)


def create_iterator(cfg: ConfigPairs) -> DataIter:
    """Build an iterator chain from one config section (reference
    data.cpp:27-94): each ``iter = <type>`` entry creates an iterator wrapping
    the previous one; every other pair is passed to all iterators in the
    chain (each ignores settings it does not understand)."""
    from . import proc, iter_imgrec, iter_img  # noqa: F401  (populate registry)
    t0 = time.perf_counter()
    kinds = [v for k, v in cfg if k == "iter"]
    params = [(k, v) for k, v in cfg if k != "iter"]
    it: Optional[DataIter] = None
    for kind in kinds:
        if kind == "end":
            continue
        if kind not in ITER_REGISTRY:
            raise ValueError(f"unknown iterator type {kind!r}")
        cls = ITER_REGISTRY[kind]
        if it is None:
            it = cls(params)
        else:
            it = cls(params, base=it)   # decorator iterators take base
        # init inner-to-outer so decorators always wrap a ready base
        it.init()
    if it is None:
        raise ValueError("config section declares no iterator")
    if any(k == "test_skipread" and str(v).strip() == "1"
           for k, v in params):
        it = SkipReadIterator(it)
    # what the chain's init() did (a synthetic source makes its rows
    # here) is set-up, as its first batch is (``__iter__``)
    TRACER.add_complete("setup.input", t0, time.perf_counter(),
                        cat="setup")
    return it
