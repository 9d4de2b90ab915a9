"""What the layer metrics that read the PROGRAM's own names share.

Since PR 24 the program names what it puts on the device — a
``jax.named_scope`` per graph layer, ``optimizer`` around the update,
``fused.<kind>`` around every fused op, ``jvp``/``transpose`` from
autodiff — and keeps its loop's ``train.*`` spans in the tracer's ring
by default. Two reductions, each returning ``None`` where there is
nothing to read (no device trace on the CPU rehearsal; a program that
has no scope table or keeps no spans, as the parent of that PR does):
a metric is then left out, never guessed.

* :func:`scoped_ms_per_step` — own device time per step of the first
  device's instructions (``view["trace"]["devices"][0]["by_name"]``,
  keyed ``"<class> <instruction>"``) whose scope, looked up in the
  compiled step's ``scope_table`` and read by ``traceparse.classify``
  as ``(phase, layer, kind)``, a predicate accepts. Each instruction is
  counted once, by its own time, so the phases and the unattributed
  rest add up to the busy time.
* :func:`span_ms_per_step` — time per step in one of the loop's spans,
  over the benchmark's un-profiled window: ``view["spans"]`` are on the
  same ``time.perf_counter`` and give the window's start,
  ``view["span_window_s"]`` its length; a step is one
  ``train.step_dispatch``.
"""


def _program():
    try:
        from cxxnet_tpu.telemetry.profiler import step_scope_table
        from cxxnet_tpu.telemetry.traceparse import classify
    except ImportError:             # a program from before PR 24
        return None
    table = step_scope_table()
    return (table, classify) if table else None


def scoped_seconds(view, want):
    """``(seconds that want(phase, layer, kind) accepts, seconds of all
    instructions, steps)`` on the first device, or ``None``."""
    if view.get("trace") is None:
        return None
    got = _program()
    if got is None:
        return None
    table, classify = got
    dev = view["trace"]["devices"][0]
    hit = total = 0.0
    for key, seconds in dev["by_name"].items():
        name = key.rsplit(" ", 1)[-1].lstrip("%")
        total += seconds
        if want(*classify(table.get(name))):
            hit += seconds
    return hit, total, dev["steps"]


def scoped_ms_per_step(view, want):
    got = scoped_seconds(view, want)
    if got is None:
        return None
    hit, _, steps = got
    return 1e3 * hit / steps


def span_ms_per_step(view, name):
    if not view["spans"] or view["span_window_s"] <= 0:
        return None
    try:
        from cxxnet_tpu.telemetry.trace import TRACER
    except ImportError:
        return None
    lo = TRACER.to_ts_us(min(t0 for _, t0, _ in view["spans"]))
    hi = lo + view["span_window_s"] * 1e6
    inside = [ev for ev in TRACER.events()
              if ev.get("ph") == "X" and lo <= ev["ts"] < hi]
    steps = sum(ev["name"] == "train.step_dispatch" for ev in inside)
    if not steps:
        return None                 # the ring kept no train spans
    return 1e-3 * sum(ev["dur"] for ev in inside
                      if ev["name"] == name) / steps
