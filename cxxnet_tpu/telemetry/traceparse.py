"""A profiler dump of the train step, read under the program's own names.

The program names what it puts on the device: ``Network.apply`` traces
every graph layer under ``jax.named_scope(<layer>)``, the step builders
trace the parameter update under ``optimizer``, and autodiff itself wraps
the forward in ``jvp(..)`` and the backward in ``transpose(jvp(..))``.
All of it lands in ``metadata={op_name="..."}`` of the compiled module's
instructions. This file is the way back: :func:`scope_table` maps a
compiled module's instruction names to those paths, :func:`classify`
reads a path as ``(phase, layer, kind)`` — the one definition of a
phase — and :func:`attribute_profile` joins them to a
``jax.profiler`` dump.

What it knows about a dump was read off real traces of the flagship
step on a TPU v5e (jax 0.9.0, libtpu 0.0.34; PERF.md section 3): a chip
is the plane ``/device:TPU:<n>``; its line ``XLA Modules`` has one event
per executable run (the one with most time is the train step, and the
first of a dump is cut off where the profiler started); its line ``XLA
Ops`` has one event per executed instruction, nested or sequential,
named by the instruction's whole HLO text and carrying no category
stat — an op's own time is its length less the events nested in it;
the plane ``Task Environment`` carries ``profile_start_time``, the Unix
nanosecond the dump's times count from, through which the loop's own
``train.*`` spans (telemetry/trace.py, on ``perf_counter``) reach the
dump's clock and name its idle gaps, the innermost span winning.

Only a chip's dump reads: the CPU backend has no device plane, and
:func:`attribute_profile` then returns ``None`` rather than guess.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
ENV_PLANE = "Task Environment"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: the phases of a train step, in the order tables print them
PHASE_ORDER = ("forward", "backward", "optimizer", "other")
#: how many of the heaviest layers / longest idle gaps a table names
TOP_N, TOP_GAPS = 8, 5

# -- from the compiled text to scopes ----------------------------------------

_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = ")
_EVENT = re.compile(r"^%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")


def scope_table(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` (names without the ``%``) for
    every instruction of a compiled module's text that carries one. A
    fusion left without metadata gets its root's (else the first its
    computation holds); an instruction the compiler made of nothing the
    program traced — a layout ``copy``, a ``bitcast`` — stays out."""
    table: Dict[str, str] = {}
    root_of: Dict[str, str] = {}
    first_of: Dict[str, str] = {}
    orphans: List[Tuple[str, str]] = []     # (instruction, computation)
    cur = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                cur = c.group(1)
            continue
        is_root, name = m.groups()
        op = _OP_NAME.search(line)
        if op:
            table[name] = op.group(1)
            first_of.setdefault(cur, op.group(1))
            if is_root:
                root_of[cur] = op.group(1)
        else:
            calls = _CALLS.search(line)
            if calls:
                orphans.append((name, calls.group(1)))
    for name, comp in orphans:
        scope = root_of.get(comp) or first_of.get(comp)
        if scope:
            table[name] = scope
    return table


_WRAPPER = re.compile(r"^([A-Za-z_][\w.\-]*)\((.*)\)$")
#: wrappers that hold a function's name, not a scope's
_FUNCTIONS = frozenset({"jit", "pjit"})
#: names jax's own control flow and call primitives put on the stack
_STRUCTURE = frozenset({
    "while", "body", "cond", "scan", "checkpoint", "remat",
    "rematted_computation", "closed_call", "core_call", "shard_map",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_lin", "pallas_call"})
#: forward work the step traces outside ``value_and_grad``
_FORWARD_SCOPES = frozenset({"input_fold"})


def scope_path(op_name: str) -> Tuple[frozenset, List[str]]:
    """An ``op_name`` taken apart: the transforms that wrap any of its
    components (``jvp``, ``transpose``, ``jit``..) and the named scopes
    in order, outermost first. The last component is the primitive's
    own name and a ``jit(..)`` holds a function's: neither is a scope."""
    transforms, scopes = set(), []
    for part in op_name.split("/")[:-1]:
        wraps = []
        m = _WRAPPER.match(part)
        while m:
            wraps.append(m.group(1))
            part = m.group(2)
            m = _WRAPPER.match(part)
        transforms.update(wraps)
        if part and not _FUNCTIONS.intersection(wraps) \
                and not part.startswith("branch_") \
                and part not in _STRUCTURE:
            scopes.append(part)
    return frozenset(transforms), scopes


def classify(scope: Optional[str]) -> Tuple[str, str, str]:
    """``(phase, layer, kind)`` of one ``op_name``. ``phase``:
    ``backward`` under ``transpose(..)``, else ``optimizer`` under the
    scope of that name, else ``forward`` under ``jvp(..)`` (or a
    forward scope outside autodiff: ``input_fold``), else ``other`` —
    which is also where an instruction without a scope goes. ``layer``:
    the outermost scope that is not a fused op's (a graph layer's name,
    ``optimizer``, ``input_fold``), ``kind``: the ``<kind>`` of the
    first ``fused.<kind>``; each ``""`` where there is none. No step
    of this tree traces a ``fused.<kind>`` scope any more (the Pallas
    suite that did left in PR 30); the benchmark's recorded traces were
    cut with it in, and its tests pin the numbers they give."""
    if not scope:
        return "other", "", ""
    transforms, scopes = scope_path(scope)
    kind = next((s[6:] for s in scopes if s.startswith("fused.")), "")
    layer = next((s for s in scopes if not s.startswith("fused.")), "")
    if "transpose" in transforms:
        phase = "backward"
    elif "optimizer" in scopes:
        phase = "optimizer"
    elif "jvp" in transforms or _FORWARD_SCOPES.intersection(scopes):
        phase = "forward"
    else:
        phase = "other"
    return phase, layer, kind


# -- the dump -----------------------------------------------------------------


def find_xplane(dump: str) -> str:
    """``dump`` itself if it is a file, else the newest ``.xplane.pb``
    under a ``start_trace`` directory."""
    if os.path.isfile(dump):
        return dump
    found = sorted(glob.glob(os.path.join(
        dump, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {dump!r}")
    return found[-1]


def _load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _own_times(events):
    """``[(name, start, end, own ns)]`` of ``(name, start, dur)`` events
    sorted by ``(start, -dur)``: a ``while`` or a call spans the ops of
    its body and must not count them twice."""
    own = [e[2] for e in events]
    stack: List[int] = []
    for i, (_, start, dur) in enumerate(events):
        while stack and events[stack[-1]][1] + events[stack[-1]][2] \
                <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [(n, s, s + d, max(0, t))
            for (n, s, d), t in zip(events, own)]


def _gaps(intervals, lo, hi):
    """The parts of ``[lo, hi)`` that no ``(start, end)`` covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def place_spans(spans: Sequence[dict], tracer, clock: Tuple[int, float],
                profile_start_unix_ns: int):
    """Ring events (telemetry/trace.py: ``ts``/``dur`` in microseconds
    of the tracer's ``perf_counter`` epoch) as ``(name, start ns, end
    ns)`` on a dump's clock; ``clock`` is the ``(time.time_ns(),
    time.perf_counter())`` pair taken when the profiler started."""
    unix_ns, perf_s = clock
    # the ring's ``ts`` of the clock moment is to_ts_us(perf_s); on the
    # dump's clock that moment is unix_ns - profile_start
    shift = unix_ns - profile_start_unix_ns - tracer.to_ts_us(perf_s) * 1e3
    return [(ev["name"], shift + ev["ts"] * 1e3,
             shift + (ev["ts"] + ev["dur"]) * 1e3)
            for ev in spans if ev.get("ph") == "X"]


def gap_owner(gap, host) -> str:
    """Which host span covers most of an idle gap (``elsewhere`` when
    none covers any of it); of spans that cover as much, the shortest:
    the innermost says most."""
    best, most = "elsewhere", (0.0, 0.0)
    for name, s, e in host:
        cover = min(gap[1], e) - max(gap[0], s)
        if cover > 0 and (cover, s - e) > most:
            best, most = name, (cover, s - e)
    return best


def attribute_profile(dump: str, hlo_text: Optional[str] = None,
                      clock: Optional[Tuple[int, float]] = None,
                      spans: Optional[Sequence[dict]] = None
                      ) -> Optional[dict]:
    """Read the newest dump under ``dump`` (or the file ``dump``) and
    attribute the first chip's op time to phase x kind x layer through
    the step's scopes. ``hlo_text``: the compiled step's text (default:
    ``profiler.step_hlo_text()``, what the trainer's last ``update()``
    ran). ``clock``: see :func:`place_spans`; with it the longest idle
    gaps are named by ``spans`` (default: the tracer's ``train``
    spans). Per-step milliseconds over whole step periods::

        {"steps", "step_ms", "busy_ms", "idle_pct", "device",
         "phases": {phase: {"ms", "pct", "count"}},       # pct of busy
         "kinds": {phase: {kind or "xla": ms}},
         "layers": [(layer, ms)], "unattributed_pct",
         "top_unattributed": [(instruction, ms)],
         "idle_gaps": [(span name, ms)]}

    ``None`` where the dump has no chip's plane or no whole step; raises
    ``FileNotFoundError`` where there is no dump."""
    from .trace import TRACER
    profile = _load(find_xplane(dump))
    ops = modules = None
    device = ""
    start_unix_ns = None
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE) and ops is None:
            device = plane.name
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = sorted(((e.name, int(e.start_ns),
                                   int(e.duration_ns))
                                  for e in line.events),
                                 key=lambda e: (e[1], -e[2]))
                elif line.name == MODULES_LINE:
                    modules = [(e.name, int(e.start_ns),
                                int(e.duration_ns)) for e in line.events]
        elif plane.name == ENV_PLANE:
            start_unix_ns = next((v for k, v in plane.stats
                                  if k == "profile_start_time"), None)
    if not ops or not modules:
        return None
    by_module: Dict[str, int] = {}
    for name, _, dur in modules:
        by_module[name] = by_module.get(name, 0) + dur
    step = max(by_module, key=by_module.get)
    starts = sorted(s for n, s, _ in modules if n == step)
    if len(starts) < 3:
        return None
    # whole periods, gaps included; the first run is cut off
    lo, hi, steps = starts[1], starts[-1], len(starts) - 2
    if hlo_text is None:
        from .profiler import step_scope_table
        table = step_scope_table()
    else:
        table = scope_table(hlo_text)
    per = 1e-6 / steps                      # ns -> ms per step
    phases = {p: {"ms": 0.0, "pct": 0.0, "count": 0} for p in PHASE_ORDER}
    kinds: Dict[str, Dict[str, float]] = {p: {} for p in PHASE_ORDER}
    layers: Dict[str, float] = {}
    loose: Dict[str, float] = {}
    busy = []
    for text, s, e, own in _own_times(ops):
        if not lo <= s < hi:
            continue
        busy.append((s, min(e, hi)))
        m = _EVENT.match(text)
        name = m.group(1) if m else text[:48]
        phase, layer, kind = classify(table.get(name))
        d = phases[phase]
        d["ms"] += own * per
        d["count"] += 1
        k = kinds[phase]
        k[kind or "xla"] = k.get(kind or "xla", 0.0) + own * per
        if phase == "other":
            loose[name] = loose.get(name, 0.0) + own * per
        elif layer:
            layers[layer] = layers.get(layer, 0.0) + own * per
    gaps = _gaps(busy, lo, hi)
    busy_ms = ((hi - lo) - sum(b - a for a, b in gaps)) * per
    op_ms = sum(d["ms"] for d in phases.values())
    for d in phases.values():
        d["pct"] = 100.0 * d["ms"] / op_ms if op_ms else 0.0
        d["count"] = round(d["count"] / steps)
    host = []
    if clock is not None and start_unix_ns is not None:
        if spans is None:
            spans = [ev for ev in TRACER.events()
                     if ev.get("cat") == "train"]
        host = place_spans(spans, TRACER, clock, int(start_unix_ns))
    top = lambda d, n: sorted(d.items(), key=lambda kv: -kv[1])[:n]
    return {
        "steps": steps, "device": device,
        "step_ms": (hi - lo) * per, "busy_ms": busy_ms,
        "idle_pct": 100.0 * (1.0 - busy_ms / ((hi - lo) * per)),
        "phases": phases,
        "kinds": {p: dict(top(k, len(k))) for p, k in kinds.items() if k},
        "layers": top(layers, TOP_N),
        "unattributed_pct": phases["other"]["pct"],
        "top_unattributed": top(loose, TOP_N),
        "idle_gaps": [(gap_owner(g, host), (g[1] - g[0]) * 1e-6)
                      for g in sorted(gaps, key=lambda g: g[0] - g[1])
                      [:TOP_GAPS]] if host else [],
    }


def attribution_fragment(att: Optional[dict]) -> str:
    """One-line round-log rendering of an attribution (main.py prints it
    after a ``telemetry_profile_steps`` bracket closes): the phases, the
    fused kinds inside each, the heaviest layers and the longest idle
    gaps by the ``train.*`` span that covers them."""
    if not att:
        return ""
    ms = lambda v: f"{v:.2f}ms"
    parts = [f"{p}:{ms(d['ms'])}({d['pct']:.0f}%)"
             for p, d in att["phases"].items() if d["count"] or d["ms"]]
    out = (f"profile[step:{ms(att['step_ms'])} "
           f"idle:{att['idle_pct']:.1f}% " + " ".join(parts) + "]")
    fused = [f"{p}/{k}:{ms(v)}" for p, ks in att["kinds"].items()
             for k, v in ks.items() if k != "xla"]
    if fused:
        out += " kinds[" + " ".join(fused) + "]"
    if att["layers"]:
        out += " layers[" + " ".join(
            f"{n}:{ms(v)}" for n, v in att["layers"]) + "]"
    if att["idle_gaps"]:
        out += " gaps[" + " ".join(
            f"{n}:{ms(v)}" for n, v in att["idle_gaps"]) + "]"
    return out
