"""The model's selection log: which implementation each site that
chooses one took.

Two kinds of site choose today, both from what the code observes, never
from an option: an attention layer (``mha``, ``mla``, ``gqa`` / ``dsa``:
``attn_impl = auto`` decides per backend and sequence length —
ops/attention.py) and a ``moe`` layer's grouped product. Every other op has one implementation,
XLA's own (PERF.md section 6, PR 26 and PR 30: the Pallas suite that
lived beside this file lost every benchmark cell and left the tree).

The module keeps the name it had as that suite's plumbing because the
benchmark's harness imports ``selection_counts`` from here and reads
``Network.fused_log`` (ROADMAP "named debts", a).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
from typing import Dict, Tuple

#: the (log, site name) the layer being traced reports into — bound by
#: :func:`selection_site` around one layer's apply
_SITE: contextvars.ContextVar = contextvars.ContextVar(
    "cxxnet_selection_site", default=None)

#: site name -> ("attention", implementation) | ("grouped", product)
SelectionLog = Dict[str, Tuple[str, str]]


@contextlib.contextmanager
def selection_site(log: SelectionLog, name: str):
    """Route :func:`note_attention` / :func:`note_grouped` calls made
    while tracing site ``name`` (a layer) into ``log``, which the model
    owns. Keyed by site, so a retrace overwrites its own entry instead
    of counting twice."""
    token = _SITE.set((log, name))
    try:
        yield
    finally:
        _SITE.reset(token)


def _record(kind: str, what: str) -> None:
    site = _SITE.get()
    if site is not None:
        site[0][site[1]] = (kind, what)


def note_attention(impl: str) -> None:
    """Record which attention implementation the attention layer being
    traced selected (``attn_impl = auto`` decides per backend and
    sequence length): ``ref`` / ``chunked`` / ``flash`` / ``ring`` /
    ``gather_kv`` for ``mha``, ``mla.<impl>`` for latent attention,
    ``gqa.<impl>`` for grouped-query attention, whose kernel with a
    window is ``gqa.flash_window`` and, over the keys its indexer
    selects (the kind ``dsa``), ``gqa.flash_sparse`` (XLA's dots there:
    ``gqa.ref_sparse``)."""
    _record("attention", impl)


def note_grouped(impl: str) -> None:
    """Record which grouped matrix product the moe layer being traced
    runs its held experts on (``ragged_dot``: XLA's own)."""
    _record("grouped", impl)


def selection_counts(log: SelectionLog):
    """{kind: Counter(what)} over the log's sites (kind: ``attention``
    / ``grouped``)."""
    by = collections.defaultdict(collections.Counter)
    for kind, what in log.values():
        by[kind][what] += 1
    return by


def selection_summary(log: SelectionLog) -> str:
    """One line: how many sites took which implementation, by kind."""
    return "selection: " + "; ".join(
        f"{kind}: " + ", ".join(f"{k}={v}" for k, v in sorted(c.items()))
        for kind, c in sorted(selection_counts(log).items()))
