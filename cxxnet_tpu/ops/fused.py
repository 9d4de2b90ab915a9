"""The model's selection log: which implementation each site that
chooses one took.

Three kinds of choice are made today, all from what the code observes,
never from an option: an attention layer's (``mha``, ``mla``, ``gqa`` /
``dsa``: ``attn_impl = auto`` decides per backend and sequence length —
ops/attention.py), a ``dsa`` layer's selection of the keys its indexer
picks (the same decision, a second entry of the same site), and a
``moe`` layer's grouped product. A fourth kind is no choice but a path
taken: where a ``dsa`` layer's indexer learns, its gradients are made
in the forward pass (``index_grad``), so a run's line shows how many
layers took it. Every other op has one implementation,
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

#: (site name, kind) -> what the site took of that kind; the kinds are
#: ``attention``, ``select``, ``grouped`` and ``index_grad``
SelectionLog = Dict[Tuple[str, str], str]


@contextlib.contextmanager
def selection_site(log: SelectionLog, name: str):
    """Route :func:`note_attention` / :func:`note_select` /
    :func:`note_grouped` / :func:`note_index_grad` calls made while
    tracing site ``name`` (a layer) into ``log``, which the model owns.
    Keyed by site and kind, so a site keeps one entry of each kind it
    reports and a retrace overwrites its own entry instead of counting
    twice."""
    token = _SITE.set((log, name))
    try:
        yield
    finally:
        _SITE.reset(token)


def _record(kind: str, what: str) -> None:
    site = _SITE.get()
    if site is not None:
        site[0][(site[1], kind)] = what


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


def note_select(impl: str) -> None:
    """Record how the ``dsa`` layer being traced selects the keys its
    indexer picks: ``gqa.select_rows`` (the kernel) or ``gqa.select_ref``
    (XLA's counting passes, ``select_topk_reference``)."""
    _record("select", impl)


def note_index_grad(where: str) -> None:
    """Record where the ``dsa`` layer being traced makes its indexer's
    gradients: ``gqa.forward``, in the forward pass, where they are kept
    for the backward (``layers/seq.py:_index_learned``). A layer whose
    indexer does not learn (not training, or ``index_loss_coef = 0``)
    records nothing."""
    _record("index_grad", where)


def note_grouped(impl: str) -> None:
    """Record which grouped matrix product the moe layer being traced
    runs its held experts on (``ragged_dot``: XLA's own)."""
    _record("grouped", impl)


def selection_counts(log: SelectionLog):
    """{kind: Counter(what)} over the log's sites, the kinds in their
    names' order (``attention``, ``grouped``, ``index_grad``,
    ``select``)."""
    by = collections.defaultdict(collections.Counter)
    for (_, kind), what in sorted(log.items(), key=lambda e: e[0][1]):
        by[kind][what] += 1
    return by


def selection_summary(log: SelectionLog) -> str:
    """One line: how many sites took which implementation, by kind."""
    return "selection: " + "; ".join(
        f"{kind}: " + ", ".join(f"{k}={v}" for k, v in sorted(c.items()))
        for kind, c in sorted(selection_counts(log).items()))
