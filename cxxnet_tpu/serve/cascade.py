"""Cascade inference: a confidence router in front of a two-tier fleet.

The cheap tier (int8-quantized replicas, ops/quant.py) answers
every request first; rows whose prediction confidence clears
``cascade_threshold`` are final, the rest escalate to the flagship
(full-precision) tier. The cost model is the classic cascade win:
every row pays the cheap tier, only the escalated fraction pays the
flagship, so cost-per-request ~= fast_latency + escalation_rate *
flagship_latency — tools/loadgen.py measures exactly that line for
``SERVE_r03.json``.

Confidence per row over the fast tier's raw output (softmax probs):

- ``margin``:  p1 - p2 (top-two gap), the standard cascade rule;
- ``entropy``: 1 - H(p)/log(k), normalized so 1 = one-hot certain.

Rows from models with a single output column (regression heads) have
no class distribution to be confident about — they never escalate.

:class:`CascadeRouter` IS a :class:`ReplicaPool` over both tiers'
replicas (tier membership = model version: the quantized round serves
as ``rNNNN-int8``, the source round as ``rNNNN``), so the ServeServer
pool surface — health, /statz, drain, version pinning, per-version
outcome stats — works unchanged; only ``submit`` adds the routing.
Version-pinned requests and ``extract`` (feature taps have no
confidence semantics) bypass the cascade and route directly.
"""

from __future__ import annotations

import threading
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..config import QuantConfig
from ..telemetry.ledger import LEDGER
from ..telemetry.registry import REGISTRY
from .fleet import Replica, ReplicaPool

_TINY = 1e-12


def row_confidence(probs: np.ndarray, metric: str = "margin") -> np.ndarray:
    """Per-row confidence in [0, 1] from raw output rows. Rows are
    defensively renormalized (the fast tier's top node is softmax in
    every served graph, but a linear head must not produce NaN
    confidences)."""
    p = np.asarray(probs, np.float64)
    if p.ndim != 2:
        p = p.reshape(p.shape[0], -1)
    k = p.shape[1]
    if k < 2:
        return np.ones(p.shape[0])
    p = np.clip(p, 0.0, None)
    p = p / np.maximum(p.sum(axis=1, keepdims=True), _TINY)
    if metric == "entropy":
        h = -np.sum(p * np.log(np.maximum(p, _TINY)), axis=1)
        return 1.0 - h / np.log(k)
    top2 = np.partition(p, k - 2, axis=1)[:, -2:]
    return top2[:, 1] - top2[:, 0]


class CascadeRouter(ReplicaPool):
    """Two-tier pool with confidence routing (see module docstring).
    Build with :meth:`build_two_tier`; or pass pre-built replicas plus
    the two tier version strings directly (tests)."""

    def __init__(self, replicas: Sequence[Replica], *,
                 fast_version: str, flagship_version: str,
                 qc: QuantConfig, admission_control: bool = True):
        super().__init__(replicas, admission_control=admission_control)
        if fast_version == flagship_version:
            raise ValueError(
                "cascade tiers must serve distinct versions, both are "
                f"{fast_version!r} (the quantized round serves with an "
                "-int8 suffix — did both tiers load the same blob?)")
        for want in (fast_version, flagship_version):
            if not any(r.version == want for r in self.replicas):
                raise ValueError(
                    f"cascade: no replica serves tier version {want!r}; "
                    f"have {sorted(self.versions())}")
        self.fast_version = fast_version
        self.flagship_version = flagship_version
        self.threshold = float(qc.cascade_threshold)
        self.metric = qc.cascade_metric
        self._clock = threading.Lock()
        self._cstats = {"requests": 0, "requests_escalated": 0,
                        "rows": 0, "rows_escalated": 0, "failed": 0}
        self._c_rows = REGISTRY.counter(
            "cxxnet_cascade_rows_total",
            "Cascade rows by final answering tier",
            labels=("pool", "tier"))
        self._g_esc = REGISTRY.gauge(
            "cxxnet_cascade_escalation_rate",
            "Fraction of cascade rows escalated to the flagship tier",
            labels=("pool",))

    # -- construction ----------------------------------------------------
    @classmethod
    def build_two_tier(cls, cfg: Any, *, flagship_blob: Dict[str, Any],
                       fast_blob: Dict[str, Any], qc: QuantConfig,
                       n_flagship: int = 1, n_fast: int = 1,
                       flagship_digest: str = "", fast_digest: str = "",
                       flagship_dtype: Optional[str] = None,
                       admission_control: bool = True,
                       silent: bool = False, **pool_kw) -> "CascadeRouter":
        """Build both tiers over the same net config: ``n_fast``
        int8 replicas on the quantized blob plus ``n_flagship``
        full-precision replicas on the source blob, merged into one
        router. Device slicing happens per tier (on CPU sessions the
        tiers share the host device, which is exactly the measurement
        mode SERVE_r03 documents)."""
        fast = ReplicaPool.build(
            cfg, n_fast, blob=fast_blob, digest=fast_digest,
            dtype="int8", admission_control=admission_control,
            silent=silent, **pool_kw)
        flagship = ReplicaPool.build(
            cfg, n_flagship, blob=flagship_blob, digest=flagship_digest,
            dtype=flagship_dtype, admission_control=admission_control,
            silent=silent, **pool_kw)
        replicas: List[Replica] = list(fast.replicas) + \
            list(flagship.replicas)
        for i, rep in enumerate(replicas):
            rep.idx = i
        return cls(replicas,
                   fast_version=fast.replicas[0].version,
                   flagship_version=flagship.replicas[0].version,
                   qc=qc, admission_control=admission_control)

    # -- routing ---------------------------------------------------------
    def submit(self, data, kind: str = "predict",
               node: Optional[str] = None,
               timeout_ms: Optional[float] = None,
               version: Optional[str] = None):
        """Confidence-routed submit. ``predict``/``raw`` requests run
        the cascade; an explicit ``version`` pin or ``extract`` routes
        directly (both legs still land in the per-version outcome
        stats via the base pool)."""
        if version is not None or kind == "extract":
            return super().submit(data, kind, node, timeout_ms, version)
        rows = np.asarray(data)
        out: "Future[np.ndarray]" = Future()
        fast_fut = super().submit(rows, "raw", None, timeout_ms,
                                  self.fast_version)
        fast_fut.add_done_callback(
            lambda f: self._on_fast(f, rows, kind, timeout_ms, out))
        return out

    def _finalize(self, out: Future, result=None, exc=None) -> None:
        if exc is not None:
            with self._clock:
                self._cstats["failed"] += 1
            out.set_exception(exc)
        else:
            out.set_result(result)

    def _on_fast(self, f: Future, rows: np.ndarray, kind: str,
                 timeout_ms: Optional[float], out: Future) -> None:
        exc = f.exception()
        if exc is not None:
            self._finalize(out, exc=exc)
            return
        try:
            probs = np.asarray(f.result())
            conf = row_confidence(probs, self.metric)
            esc = conf < self.threshold
            n, n_esc = len(conf), int(esc.sum())
            with self._clock:
                self._cstats["requests"] += 1
                self._cstats["rows"] += n
                self._cstats["rows_escalated"] += n_esc
                if n_esc:
                    self._cstats["requests_escalated"] += 1
                rate = self._cstats["rows_escalated"] \
                    / max(1, self._cstats["rows"])
            self._c_rows.labels(self.instance, "fast").inc(n - n_esc)
            self._g_esc.labels(self.instance).set(rate)
            if not n_esc:
                self._finalize(out, self._fast_answer(probs, kind))
                return
            self._c_rows.labels(self.instance, "flagship").inc(n_esc)
            LEDGER.event("cascade_escalate", rows=n_esc, total=n,
                         min_conf=round(float(conf.min()), 4),
                         threshold=self.threshold, metric=self.metric)
            flag_fut = ReplicaPool.submit(
                self, rows[esc], kind, None, timeout_ms,
                self.flagship_version)
            flag_fut.add_done_callback(
                lambda g: self._on_flagship(g, probs, esc, kind, out))
        except Exception as e:                  # noqa: BLE001
            self._finalize(out, exc=e)

    def _on_flagship(self, g: Future, probs: np.ndarray,
                     esc: np.ndarray, kind: str, out: Future) -> None:
        exc = g.exception()
        if exc is not None:
            self._finalize(out, exc=exc)
            return
        try:
            merged = self._fast_answer(probs, kind)
            flag = np.asarray(g.result())
            merged[esc] = flag
            self._finalize(out, merged)
        except Exception as e:                  # noqa: BLE001
            self._finalize(out, exc=e)

    @staticmethod
    def _fast_answer(probs: np.ndarray, kind: str) -> np.ndarray:
        """Fast-tier rows in the requested output kind (matching the
        engine's predict semantics: argmax, raw scalar for 1-col)."""
        if kind == "raw":
            return np.array(probs, np.float32)
        p = probs.reshape(probs.shape[0], -1)
        if p.shape[1] == 1:
            return p[:, 0].astype(np.float32)
        return np.argmax(p, axis=1).astype(np.float32)

    # -- introspection ---------------------------------------------------
    def cascade_stats(self) -> Dict[str, Any]:
        with self._clock:
            s = dict(self._cstats)
        s.update(
            threshold=self.threshold, metric=self.metric,
            fast_version=self.fast_version,
            flagship_version=self.flagship_version,
            escalation_rate=round(
                s["rows_escalated"] / max(1, s["rows"]), 6))
        return s

    def snapshot(self) -> Dict[str, Any]:
        out = super().snapshot()
        out["cascade"] = self.cascade_stats()
        return out
