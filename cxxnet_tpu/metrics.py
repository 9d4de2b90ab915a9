"""Evaluation metrics: rmse, error, logloss, rec@n.

Reference: MetricSet (/root/reference/src/utils/metric.h:25-271) and the
``metric[...]`` config binding (nnet_impl-inl.hpp:73-83). Metrics accumulate
(sum, count) host-side over numpy prediction/label slices; padded rows
(num_batch_padd) are excluded by the caller passing only real rows, matching
the reference (nnet_impl-inl.hpp:263-265). In distributed runs the (sum,count)
pair is what gets all-reduced (the reference rabit-allreduces inside Get(),
metric.h:60-68); ``MetricSet.merge`` / ``psum_pairs`` provide that hook.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np


class Metric:
    def __init__(self, name: str, label_field: str):
        self.name = name
        self.label_field = label_field
        self.sum = 0.0
        self.cnt = 0

    def clear(self) -> None:
        self.sum, self.cnt = 0.0, 0

    def add(self, pred: np.ndarray, label: np.ndarray) -> None:
        """pred: (n, k) scores; label: (n, w)."""
        raise NotImplementedError

    def get(self) -> float:
        return self.sum / max(self.cnt, 1)

    #: whether ``add_reduced`` can stand in for ``add``: the trainer then
    #: fetches a loss head's (n, 3) reduction instead of its node
    takes_reduced = False

    def add_reduced(self, stats: np.ndarray) -> None:
        """stats: (n, 3) per row — summed log-probability of the labels,
        argmax hits, positions counted — reduced on the device by the
        loss head the node belongs to, against that head's own labels."""
        raise NotImplementedError

    @staticmethod
    def _positions(stats: np.ndarray) -> int:
        return int(round(float(np.sum(stats[:, 2]))))


class MetricRMSE(Metric):
    def add(self, pred, label):
        self.sum += float(np.sum((pred - label) ** 2))
        self.cnt += pred.shape[0]


class MetricError(Metric):
    """Classification error: argmax vs label when pred has >1 column and
    label_width==1; sign threshold at 0 otherwise (metric.h:104-136)."""

    def add(self, pred, label):
        n = pred.shape[0]
        if label.shape[1] != 1:
            guess = (pred > 0.0).astype(np.int64)
            err = np.mean(guess != label.astype(np.int64), axis=1)
            self.sum += float(np.sum(err))
        elif pred.shape[1] != 1:
            guess = np.argmax(pred, axis=1)
            self.sum += float(np.sum(guess != label[:, 0].astype(np.int64)))
        else:
            guess = (pred[:, 0] > 0.0).astype(np.int64)
            self.sum += float(np.sum(guess != label[:, 0].astype(np.int64)))
        self.cnt += n


class MetricLogloss(Metric):
    def add(self, pred, label):
        n = pred.shape[0]
        p = np.clip(pred, 1e-15, 1 - 1e-15)
        if label.shape[1] != 1:
            t = label.astype(np.float64)
            ll = -(t * np.log(p[:, :1]) + (1 - t) * np.log(1 - p[:, :1]))
            self.sum += float(np.sum(np.mean(ll, axis=1)))
        elif pred.shape[1] != 1:
            idx = label[:, 0].astype(np.int64)
            self.sum += float(np.sum(-np.log(p[np.arange(n), idx])))
        else:
            t = label[:, 0].astype(np.float64)
            self.sum += float(np.sum(-(t * np.log(p[:, 0]) +
                                       (1 - t) * np.log(1 - p[:, 0]))))
        self.cnt += n


class MetricRecall(Metric):
    """rec@n: fraction of rows whose true label is within the top-n scores
    (metric.h:170-200)."""

    def __init__(self, name, label_field):
        super().__init__(name, label_field)
        m = re.match(r"rec@(\d+)$", name)
        if not m:
            raise ValueError(f"bad recall metric name {name!r}")
        self.topn = int(m.group(1))

    def add(self, pred, label):
        n = pred.shape[0]
        if pred.shape[1] < self.topn:
            raise ValueError(
                f"rec@{self.topn} on prediction list of length {pred.shape[1]}")
        top = np.argsort(-pred, axis=1)[:, :self.topn]
        # every label column counts; per-row score = hits / label count
        # (reference metric.h:170-200 loops all label fields)
        idx = label.astype(np.int64)                    # (n, w)
        hits = np.any(top[:, None, :] == idx[:, :, None], axis=2)  # (n, w)
        self.sum += float(np.sum(hits.mean(axis=1)))
        self.cnt += n


class MetricSeqError(Metric):
    """Per-token classification error for sequence models: pred is the
    flattened (n, S*V) per-token probabilities, label is (n, S) token ids
    (V inferred as pred_cols // label_cols). Extension metric — the
    reference has no sequence axis."""

    def add(self, pred, label):
        n, S = label.shape
        V = pred.shape[1] // S
        guess = np.argmax(pred.reshape(n, S, V), axis=2)
        self.sum += float(np.sum(guess != label.astype(np.int64)))
        self.cnt += n * S

    takes_reduced = True

    def add_reduced(self, stats):
        self.sum += float(np.sum(stats[:, 2] - stats[:, 1]))
        self.cnt += self._positions(stats)


class MetricSeqLogloss(Metric):
    """Mean negative log-probability of the label per position: pred is
    the flattened (n, S*V) per-token LOG-probabilities of an ``lmloss``
    node, label (n, S) token ids."""

    def add(self, pred, label):
        n, S = label.shape
        lp = pred.reshape(n, S, pred.shape[1] // S)
        idx = label.astype(np.int64)
        self.sum += float(-np.sum(np.take_along_axis(
            lp, idx[:, :, None], axis=2)))
        self.cnt += n * S

    takes_reduced = True

    def add_reduced(self, stats):
        self.sum += float(-np.sum(stats[:, 0]))
        self.cnt += self._positions(stats)


def create_metric(name: str, label_field: str) -> Metric:
    if name == "seq_error":
        return MetricSeqError(name, label_field)
    if name == "seq_logloss":
        return MetricSeqLogloss(name, label_field)
    if name == "rmse":
        return MetricRMSE(name, label_field)
    if name == "error":
        return MetricError(name, label_field)
    if name == "logloss":
        return MetricLogloss(name, label_field)
    if name.startswith("rec@"):
        return MetricRecall(name, label_field)
    raise ValueError(f"unknown metric {name!r}")


class MetricSet:
    """Set of metrics, each bound to a (label_field, node) pair.

    Config syntax handled by the trainer:
      ``metric = error``                 -> label field "label", top node
      ``metric[lbl,node] = error``       -> named label field + named node
    """

    def __init__(self) -> None:
        self.metrics: List[Metric] = []
        self.nodes: List[Optional[str]] = []   # None = top (last) node

    def add(self, metric_name: str, label_field: str = "label",
            node: Optional[str] = None) -> None:
        self.metrics.append(create_metric(metric_name, label_field))
        self.nodes.append(node)

    def clear(self) -> None:
        for m in self.metrics:
            m.clear()

    def add_eval(self, node_values: Dict[Optional[str], np.ndarray],
                 node_labels: Dict[Optional[str], np.ndarray],
                 label_slices: Dict[str, Tuple[int, int]],
                 reduced=()) -> None:
        """node_values maps node-name (or None for top) to (n, k) scores for
        the real (unpadded) rows this process holds; node_labels carries the
        row-aligned (n, w) label block per node (rows can differ per node in
        multi-host runs when some nodes are replicated). For a node in
        ``reduced`` the value is its loss head's (n, 3) reduction
        (``Metric.add_reduced``)."""
        for m, node in zip(self.metrics, self.nodes):
            pred = node_values[node]
            if node in reduced:
                m.add_reduced(np.asarray(pred))
                continue
            label = node_labels[node]
            a, b = label_slices[m.label_field]
            m.add(np.asarray(pred), np.asarray(label[:, a:b]))

    def get(self, prefix: str) -> List[Tuple[str, float]]:
        return [(f"{prefix}-{m.name}", m.get()) for m in self.metrics]

    def pairs(self) -> List[Tuple[float, int]]:
        """(sum, cnt) pairs for distributed reduction."""
        return [(m.sum, m.cnt) for m in self.metrics]

    def set_pairs(self, pairs: List[Tuple[float, int]]) -> None:
        for m, (s, c) in zip(self.metrics, pairs):
            m.sum, m.cnt = s, c
