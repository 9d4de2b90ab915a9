"""From a profiler dump to per-device op intervals, and the arithmetic
the per-layer metrics share.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. What this
file knows about the dump was read off real traces of the flagship step
on a v5e, one chip and four (jax 0.9.0, libtpu 0.0.34), by hand —
PERF.md "How the trace is read" — and is pinned by
``tests/benchmarks/test_benchmark_trace_reduce.py`` on a cut-down copy
of such a trace:

* a chip is a plane named ``/device:TPU:<n>``;
* its line ``XLA Modules`` holds one event per executable run
  (``jit_one(<fingerprint>)`` is the train step); the first one of a
  trace is cut off at the moment the profiler started;
* its line ``XLA Ops`` holds one event per executed HLO instruction,
  strictly sequential or nested (a few ops hold zero-length
  ``ConcatBitcast`` custom calls), never overlapping. The event's name
  is the instruction's whole HLO text — ``%fusion.92 = bf16[...]
  fusion(...), kind=kOutput, calls=%fused_computation.135`` — and it
  carries **no** category stat, so what an op is is read from that
  text: the opcode, a custom call's target (a Pallas kernel is a
  ``custom-call`` with ``custom_call_target="tpu_custom_call"``), and
  for a fusion the computation it calls, looked up in the compiled
  module's text for a ``convolution`` or ``dot``;
* its line ``Async XLA Ops`` holds the asynchronous copies and
  collectives from their ``-start`` to their ``-done``; they overlap
  each other and the ops line;
* the plane ``Task Environment`` holds no event and the stat
  ``profile_start_time``, the moment all these times count from, in
  nanoseconds of the Unix clock: with it the benchmark's own host spans
  (kept in memory; the run traces no host thread) are put on the device
  lines' clock.

All times are nanoseconds from the start of the profile.
"""

from __future__ import annotations

import glob
import os
import re
from collections import namedtuple

#: one event. ``name`` is the instruction's own name (``%fusion.92``) or
#: a host span's; ``cat`` what it is (see :func:`category`), '' on host
#: spans and module runs
Ev = namedtuple("Ev", "name cat start dur")

DEVICE_PLANE = "/device:TPU:"
ENV_PLANE = "Task Environment"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"

PALLAS = "custom-call:tpu_custom_call"
MXU_FUSION = "convolution fusion"
#: opcodes that are communication between chips (their ``-start`` and
#: ``-done`` halves too)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
#: opcodes that only move or re-tile data
RELAYOUT = ("copy", "reshape", "transpose")

_INSTR = re.compile(r"^(%?[\w.\-]+) = .*?\s([\w\-]+)\(")
_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_KIND = re.compile(r"kind=(\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")


def find_xplane(dump_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    found = sorted(glob.glob(os.path.join(
        dump_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {dump_dir}")
    return found[-1]


def mxu_computations(hlo_text: str) -> frozenset:
    """Names of the computations of a compiled module's text that hold
    a ``convolution`` or a ``dot``: a fusion that calls one runs on the
    MXU (to the TPU compiler a dot is a convolution)."""
    out, cur = set(), None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = m.group(1)
        elif cur and (" convolution(" in line or " dot(" in line):
            out.add(cur)
    return frozenset(out)


def category(text: str, mxu_calls=frozenset()):
    """``(instruction name, what it is)`` from an op event's name, the
    instruction's HLO text. What it is: ``custom-call:<target>``,
    ``convolution fusion`` for a fusion whose computation is in
    ``mxu_calls``, ``fusion:<kind>`` for any other, else the opcode."""
    m = _INSTR.match(text)
    if not m:
        return text[:64], "unknown"
    name, op = m.groups()
    if op == "custom-call":
        t = _TARGET.search(text)
        return name, "custom-call:" + (t.group(1) if t else "?")
    if op == "fusion":
        c = _CALLS.search(text)
        if c and c.group(1) in mxu_calls:
            return name, MXU_FUSION
        k = _KIND.search(text)
        return name, "fusion:" + (k.group(1) if k else "?")
    return name, op


def read(profile, mxu_calls=frozenset()) -> dict:
    """``{"devices": {plane: {"ops": [Ev], "async": [Ev],
    "modules": [Ev]}}, "start_unix_ns": int or None}`` from a
    ``ProfileData`` (or the path of an ``.xplane.pb``). ``mxu_calls``:
    :func:`mxu_computations` of the step."""
    if isinstance(profile, (str, os.PathLike)):
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(os.fspath(profile))
    keys = {OPS_LINE: "ops", ASYNC_LINE: "async", MODULES_LINE: "modules"}
    devices, start = {}, None
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                key = keys.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    name, cat = (e.name, "") if key == "modules" \
                        else category(e.name, mxu_calls)
                    lines[key].append(Ev(name, cat, int(e.start_ns),
                                         int(e.duration_ns)))
            for evs in lines.values():
                evs.sort(key=lambda e: (e.start, -e.dur))
            devices[plane.name] = lines
        elif plane.name == ENV_PLANE:
            start = dict(plane.stats).get("profile_start_time")
    return {"devices": dict(sorted(devices.items())),
            "start_unix_ns": None if start is None else int(start)}


# -- interval arithmetic ------------------------------------------------


def merge(intervals):
    """Sorted, disjoint ``[(start, end)]`` covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The part of the merged intervals ``a`` that no interval of the
    merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def spans_of(events):
    return [(e.start, e.start + e.dur) for e in events]


def self_times(events):
    """Each event's own time: its length less the events nested
    directly inside it (a ``while`` or a call spans the ops of its
    body, and must not count them twice). ``events`` sorted by
    ``(start, -dur)``, nested or sequential; returns ``[(Ev, ns)]``."""
    own = [e.dur for e in events]
    stack = []                       # indices of the events still open
    for i, e in enumerate(events):
        while stack and events[stack[-1]].start \
                + events[stack[-1]].dur <= e.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= e.dur
        stack.append(i)
    return [(e, max(0, t)) for e, t in zip(events, own)]


# -- what an op is --------------------------------------------------------


def is_collective(e: Ev) -> bool:
    return e.cat.startswith(COLLECTIVES)


def is_pallas(e: Ev) -> bool:
    return e.cat == PALLAS


def is_mxu(e: Ev) -> bool:
    """An op that holds a convolution or a dot."""
    return e.cat in (MXU_FUSION, "convolution", "dot")


def is_relayout(e: Ev) -> bool:
    return e.cat in RELAYOUT


# -- the window -------------------------------------------------------------


def step_module(modules):
    """Name of the module that takes most device time: the train step."""
    by = {}
    for m in modules:
        by[m.name] = by.get(m.name, 0) + m.dur
    return max(by, key=by.get) if by else None


def window_of(dev: dict):
    """``(lo, hi, steps)``: from the start of the second train-step run
    in the trace to the start of the last, so the window holds ``steps``
    whole step periods — the gaps between steps included — whatever
    moment the profiler started and stopped at. The first run is left
    out: it is cut off where the profiler started, and its start is not
    a step's. ``None`` with fewer than three runs."""
    name = step_module(dev["modules"])
    starts = [m.start for m in dev["modules"] if m.name == name]
    if len(starts) < 3:
        return None
    return starts[1], starts[-1], len(starts) - 2


def reduce_device(dev: dict):
    """The numbers the layer metrics share, for one chip's lines, over
    its own window. Seconds; per-class totals are of the ops' own time."""
    win = window_of(dev)
    if win is None:
        return None
    lo, hi, steps = win
    ops = [(e, t) for e, t in self_times(dev["ops"]) if lo <= e.start < hi]
    busy = merge(clip(spans_of([e for e, _ in ops]), lo, hi))
    coll = merge(clip(spans_of(
        [e for e, _ in ops if is_collective(e)]
        + [e for e in dev.get("async", ()) if is_collective(e)]), lo, hi))
    rest = merge(clip(spans_of(
        [e for e, _ in ops if not is_collective(e)]), lo, hi))
    by_cat, by_name, count = {}, {}, {}
    for e, t in ops:
        by_cat[e.cat] = by_cat.get(e.cat, 0) + t
        count[e.cat] = count.get(e.cat, 0) + 1
        key = f"{e.cat} {e.name}"
        by_name[key] = by_name.get(key, 0) + t
    ns = 1e-9

    def own(pred):
        return sum(t for e, t in ops if pred(e)) * ns
    return {
        "lo": lo, "hi": hi, "steps": steps,
        "window_s": (hi - lo) * ns,
        "busy_s": total(busy) * ns,
        "pallas_s": own(is_pallas),
        "mxu_s": own(is_mxu),
        "relayout_s": own(is_relayout),
        "collective_s": total(coll) * ns,
        "collective_exposed_s": total(subtract(coll, rest)) * ns,
        "gaps": subtract([(lo, hi)], busy),
        "by_cat": {k: (v * ns, count[k]) for k, v in by_cat.items()},
        "by_name": {k: v * ns for k, v in by_name.items()},
    }


def gap_owner(gap, host):
    """Which of the benchmark's host spans covers most of an idle gap
    (``elsewhere`` when none covers any of it); of spans that cover as
    much, the shortest — a span nested in another says more."""
    best, most = None, (0, 0)
    for e in host:
        cover = min(gap[1], e.start + e.dur) - max(gap[0], e.start)
        if cover > 0 and (cover, -e.dur) > most:
            best, most = e, (cover, -e.dur)
    return best.name if best else "elsewhere"


def breakdown(red: dict, host, n_ops=10, n_gaps=5) -> dict:
    """The contract's ``breakdown``: the classes of device op with most
    time in the window (5 000 instructions a step say less than their
    ten classes do), as ``<what it is> x<events per step>``, and the
    longest idle gaps by what the host was doing."""
    ops = sorted(red["by_cat"].items(), key=lambda kv: -kv[1][0])[:n_ops]
    gaps = sorted(red["gaps"], key=lambda g: g[0] - g[1])[:n_gaps]
    return {"device_ops": [[f"{k} x{n / red['steps']:.0f}/step", v]
                           for k, (v, n) in ops],
            "idle_gaps": [[gap_owner(g, host), (g[1] - g[0]) * 1e-9]
                          for g in gaps]}


def top_instructions(red: dict, n=15):
    """The single instructions with most time, ``[class + name, s]``."""
    return sorted(red["by_name"].items(), key=lambda kv: -kv[1])[:n]

