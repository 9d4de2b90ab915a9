"""Shared plumbing for the fused Pallas kernel suite (doc/tasks.md
"Fused kernels").

Selection contract — the one rule every fused op follows:

* ``fused_kernels = auto`` (default): a kernel is selected only for a
  kind that has won a benchmark cell on the chip against its jnp/XLA
  form — and none has (PERF.md section 6, PR 25 and PR 26: ``bn_act``,
  ``bias_act``, ``lrn``, ``pool`` and ``sgd_apply`` each lost every
  cell that reaches them, by the kernels' own time plus the
  ``copy``/``reshape`` XLA puts around their ``[rows*H*W, C]``
  operands; the kinds no cell reaches share that operand contract and
  have no chip pair). So today ``auto`` runs the jnp reference of
  every kind on every backend, TPU included.
* ``fused_kernels = 1``: kernels are selected everywhere; on the CPU
  backend they run under ``interpret=True`` (the SAME kernel code is
  exercised by CPU tests and smokes). On a TPU backend they are always
  compiled: a kernel the chip's compiler refuses raises, it never
  gives way to its reference. The explicit way back for a
  configuration no cell covers.
* ``fused_kernels = 0``: jnp references everywhere, and no relu
  folding into producers (model.py reads the knob, not the env).
* env ``CXXNET_FUSED_KERNELS`` overrides the knob's kernel selection
  with the same values (ops-level switch that needs no config edit).

Gating beyond the knob (callers, not this module): a ``pallas_call``
is an opaque custom call the GSPMD partitioner cannot shard, so on a
multi-device mesh every fused op runs inside a fully-MANUAL
``shard_map`` island (:func:`island`) whose in/out specs shard the
batch dim over the data axis — per-op collectives (the fused BN's
moment psum, the epilogue's dbias psum) make the mesh math match the
GSPMD jnp references exactly (sync-BN stays sync-BN). The trainer
hands the mesh context to the ops as a :class:`FusedSpmd` via
``Network.fused_spmd`` / ``Optimizer.fused_spmd``; topologies the
islands do not cover (pipeline stages, sp x tp) still clear the gate,
now with a one-time warning and a
``cxxnet_fused_fallback_total{reason}`` counter (:func:`note_fallback`)
instead of a silent slow path.

Every fused op returns ``None`` for unsupported shapes/dtypes and the
caller falls back to its reference implementation — counted by reason
(:func:`note_fallback`), as a selected kernel is by op
(:func:`note_fused`), into the model's selection log
(:func:`selection_site`), which the trainer prints once.

Names on the device: every wrapper traces under the scope
``fused.<op>`` that :func:`note_fused` returns, and every
``pl.pallas_call`` carries ``name="<op>_<fwd|bwd|...>"``, so a compiled
step's custom-call instruction reads ``%bn_act_bwd.3`` with ``op_name``
``.../<layer>/fused.bn_act/bn_act_bwd/pallas_call``.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import os
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax

from ..config import parse_fused_mode

#: dtypes the fused kernels accept as activation inputs; everything is
#: accumulated in f32 inside the kernels regardless.
SUPPORTED_DTYPES = ("float32", "bfloat16", "float16")

#: canonicalize a ``fused_kernels`` value -> auto|on|off (the config
#: layer owns the grammar; re-exported here for the ops-side callers)
resolve_mode = parse_fused_mode


def kernels_active(mode: str) -> bool:
    """Trace-time selection decision for a resolved mode string. The
    ``CXXNET_FUSED_KERNELS`` env var wins over the config knob. Only
    ``on`` selects kernels: ``auto`` would add the kinds that won a
    cell on the chip, and there is none (module docstring)."""
    env = os.environ.get("CXXNET_FUSED_KERNELS", "")
    if env:
        mode = resolve_mode(env)
    return mode == "on"


@dataclasses.dataclass(frozen=True)
class FusedSpmd:
    """Mesh context for shard_map-wrapped fused kernels: the mesh and
    the axis the batch's leading dim is sharded over. Hashable (Mesh
    hashes by device assignment) so it can ride custom_vjp
    nondiff_argnums."""
    mesh: Any                 # jax.sharding.Mesh
    batch_axis: str = "data"

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.batch_axis])


def island(spmd: FusedSpmd, fn, in_batch: Sequence[bool],
           out_batch: Union[bool, Sequence[bool]], interpret: bool):
    """Wrap ``fn`` in a fully-manual shard_map over EVERY mesh axis:
    args flagged True in ``in_batch`` shard their leading dim over
    ``spmd.batch_axis``, the rest replicate; ``out_batch`` likewise
    for the outputs (a bare bool for a single output). Inside the
    island GSPMD never sees the pallas_call — the body is manual —
    and any cross-shard reduction is the body's own explicit psum.

    ``interpret``: whether the body's kernels run under the Pallas
    interpreter. Compiled kernels are opaque calls whose outputs
    declare their varying axes (:func:`out_struct`), so the island
    keeps shard_map's ``check_vma``. The interpreter instead evaluates
    the kernel body op by op on the shard's values, where its own
    unvarying scratch meets varying blocks and the check refuses the
    mix — interpreted islands run unchecked (same numerics; transposes
    psum over the unmentioned axes instead of tracking them)."""
    from jax.sharding import PartitionSpec as P
    bspec = P(spmd.batch_axis)
    in_specs = tuple(bspec if b else P() for b in in_batch)
    if isinstance(out_batch, bool):
        out_specs: Any = bspec if out_batch else P()
    else:
        out_specs = tuple(bspec if b else P() for b in out_batch)
    return jax.shard_map(fn, mesh=spmd.mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=set(spmd.mesh.axis_names),
                         check_vma=not interpret)


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """One ``out_shape`` entry of a ``pallas_call`` that may sit inside
    an :func:`island`: under shard_map's ``check_vma`` the struct must
    say over which mesh axes the output varies, and a kernel's output
    varies wherever any of its ``operands`` does (the empty set outside
    a shard_map)."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def batch_divisible(spmd: Optional[FusedSpmd], leading: int) -> bool:
    """Whether the batch's leading dim splits evenly over the island's
    batch axis (callers fall back to their reference otherwise)."""
    return spmd is None or (spmd.n_shards > 0
                            and leading % spmd.n_shards == 0)


#: reasons already warned about (print once per process, count always)
_FALLBACK_WARNED = set()

#: the (log, site name) the fused ops called right now report into —
#: bound by :func:`selection_site` around one layer's apply
_SITE: contextvars.ContextVar = contextvars.ContextVar(
    "cxxnet_fused_site", default=None)

#: site name -> ("fused", op) | ("reference", reason) |
#: ("attention", implementation)
SelectionLog = Dict[str, Tuple[str, str]]


@contextlib.contextmanager
def selection_site(log: SelectionLog, name: str):
    """Route :func:`note_fused` / :func:`note_fallback` calls made while
    tracing site ``name`` (a layer, or an optimizer tag group) into
    ``log``, which the model owns. Keyed by site, so a retrace
    overwrites its own entry instead of counting twice."""
    token = _SITE.set((log, name))
    try:
        yield
    finally:
        _SITE.reset(token)


def _record(kind: str, what: str) -> None:
    site = _SITE.get()
    if site is not None:
        site[0][site[1]] = (kind, what)


def note_fused(op: str):
    """Record that the site being traced took fused kernel ``op``, and
    return the scope ``fused.<op>`` for the wrapper to trace the kernel
    call AND its own reshapes/transposes under (``with note_fused(..)``)
    — the selection log and a device trace's ``op_name`` then use the
    same word (telemetry/traceparse.classify reads it back)."""
    _record("fused", op)
    return jax.named_scope(f"fused.{op}")


def note_attention(impl: str) -> None:
    """Record which attention implementation the mha layer being
    traced selected (``attn_impl = auto`` decides per backend and
    sequence length)."""
    _record("attention", impl)


def note_grouped(impl: str) -> None:
    """Record which grouped matrix product the moe layer being traced
    runs its held experts on (``ragged_dot``: XLA's own)."""
    _record("grouped", impl)


def note_fallback(reason: str, warn: Optional[str] = None) -> None:
    """Record a fused-path fallback: the site being traced took its
    reference for ``reason``. Always bumps
    ``cxxnet_fused_fallback_total{reason}`` in the telemetry registry
    (visible in /metrics and fleet snapshots), and prints ``warn``
    once per process — a mesh run that silently loses its fused hot
    path is exactly the quiet misconfiguration telemetry exists for."""
    _record("reference", reason)
    from ..telemetry.registry import get_registry
    get_registry().counter(
        "cxxnet_fused_fallback_total",
        "fused kernel suite fallbacks to the reference path, "
        "by reason", labels=("reason",)).labels(reason).inc()
    if warn and reason not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(reason)
        print(f"fused_kernels: {warn} (reason={reason}; counted in "
              "cxxnet_fused_fallback_total)", flush=True)


def selection_counts(log: SelectionLog):
    """{kind: Counter(what)} over the log's sites (kind: ``fused`` /
    ``reference`` / ``attention``)."""
    by = collections.defaultdict(collections.Counter)
    for kind, what in log.values():
        by[kind][what] += 1
    return by


def selection_summary(log: SelectionLog) -> str:
    """One line: how many sites took a fused kernel (by op) and how
    many their reference (by reason)."""
    by = selection_counts(log)
    part = lambda c: ", ".join(f"{k}={v}" for k, v in sorted(c.items()))
    line = (f"fused_kernels: {sum(by['fused'].values())} sites fused "
            f"({part(by['fused'])}); {sum(by['reference'].values())} "
            f"took the reference ({part(by['reference'])})")
    for kind in ("attention", "grouped"):
        if by[kind]:
            line += f"; {kind}: {part(by[kind])}"
    return line


def use_interpret(interpret: Optional[bool]) -> bool:
    """The one place a kernel's ``interpret`` flag is decided: ``None``
    means compiled on a TPU backend and the Pallas interpreter on the
    CPU backend (``dev = cpu``, the tests) — the same kernel code runs
    either way."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def row_block(n: int, target: int = 256, mult: int = 8) -> Optional[int]:
    """Largest row-block size that (a) divides ``n`` exactly, (b) is a
    multiple of ``mult`` (the sublane tile: 8 for f32, 16 for
    bf16/f16 — see sublane_mult), and (c) is <= ``target`` (VMEM
    residency cap). ``None`` when ``n`` has no such divisor — the
    caller falls back to its jnp reference (no remainder masking:
    unsupported is cheaper than wrong)."""
    if n <= 0 or n % mult:
        return None
    best = None
    for b in range(mult, min(target, n) + 1, mult):
        if n % b == 0:
            best = b
    return best


def sublane_mult(x: jax.Array) -> int:
    """Min sublane tile multiple for this dtype's TPU layout: (8, 128)
    for f32, (16, 128) for the 16-bit floats."""
    import jax.numpy as jnp
    return 8 if jnp.dtype(x.dtype).itemsize == 4 else 16


def supported_dtype(x: jax.Array) -> bool:
    import jax.numpy as jnp
    return jnp.dtype(x.dtype).name in SUPPORTED_DTYPES
