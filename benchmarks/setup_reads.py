"""What the set-up metrics share: the program's own set-up spans in the
tracer's ring (``cat="setup"``, kept by default as the loop's
``train.*`` spans are), on the ``time.perf_counter`` of the benchmark's
own spans.

Set-up is what runs before the window's first fetch (the first start in
``view["spans"]``) inside a span the program opens for it, on the same
thread: ``setup.task`` (``LearnTask.__init__``), ``setup.weights``
(``LearnTask._init_model``), ``setup.input`` (an iterator chain's
construction, and its first batch) and ``train.round`` (a round of the
loop: the warm-up's whole, the window's up to its first fetch). What
runs between them — the reference check's own executables, the
harness's copy of the weights — is the benchmark's, and left out.

Inside set-up each instant goes to the innermost span over it, so the
parts add up to the set-up spans' union and a nested span is never
counted twice: a ``compile.trace`` of a function jitted inside another's
trace, a backend compile of an eager op inside a trace.
:func:`setup_seconds` returns those seconds by span name and the number
of set-up's ``compile.backend`` spans (executables built, or loaded from
the persistent cache); ``None`` where the ring holds no ``setup.*`` span
— a program that records none, or one run with ``telemetry_steptime =
0`` — and the metric is then left out.
"""

import heapq

#: the spans that open set-up; anything else in it is one of their parts
OPENERS = ("setup.task", "setup.weights", "setup.input", "train.round")


def _setup_events(view):
    """``(window start in the ring's microseconds, {tid: [event]})`` of
    the ``setup`` category before the window, or ``None``."""
    if not view["spans"]:
        return None
    try:
        from cxxnet_tpu.telemetry.trace import TRACER
    except ImportError:
        return None
    start = TRACER.to_ts_us(min(t0 for _, t0, _ in view["spans"]))
    by_tid = {}
    for ev in TRACER.events():
        if ev.get("ph") == "X" and ev.get("cat") == "setup" \
                and ev["ts"] < start:
            by_tid.setdefault(ev["tid"], []).append(ev)
    if not any(ev["name"].startswith("setup.")
               for evs in by_tid.values() for ev in evs):
        return None
    return start, by_tid


def setup_seconds(view):
    """``({span name: seconds it is innermost in set-up},
    executables)``, or ``None``."""
    got = _setup_events(view)
    if got is None:
        return None
    start, by_tid = got
    seconds, executables = {}, 0
    for evs in by_tid.values():
        spans = [(ev["ts"], min(ev["ts"] + ev["dur"], start), ev["name"])
                 for ev in evs]
        opened = [(a, b) for a, b, name in spans if name in OPENERS]
        # a sweep over the spans' edges; the innermost span over a
        # stretch is the one that started last (of those, the shortest)
        edges = sorted({t for a, b, _ in spans for t in (a, b)})
        starts = sorted(range(len(spans)), key=lambda i: spans[i][0])
        live, k = [], 0
        for lo, hi in zip(edges, edges[1:]):
            while k < len(starts) and spans[starts[k]][0] <= lo:
                a, b, _ = spans[starts[k]]
                heapq.heappush(live, (-a, b - a, b, starts[k]))
                k += 1
            while live and live[0][2] <= lo:
                heapq.heappop(live)
            if not any(a <= lo and hi <= b for a, b in opened):
                continue
            name = spans[live[0][3]][2]
            seconds[name] = seconds.get(name, 0.0) + (hi - lo) * 1e-6
        for a, b, name in spans:
            mid = (a + b) / 2
            if name == "compile.backend" \
                    and any(lo <= mid <= hi for lo, hi in opened):
                executables += 1
    return seconds, executables


def seconds_in(view, *names):
    """Set-up's seconds whose innermost span is one of ``names``."""
    got = setup_seconds(view)
    if got is None:
        return None
    return sum(got[0].get(name, 0.0) for name in names)


def executables(view):
    got = setup_seconds(view)
    return None if got is None else got[1]
