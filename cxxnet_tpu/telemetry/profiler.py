"""jax.profiler brackets, and the train step's own description.

``telemetry_profile_steps=a-b`` brackets exactly the global steps
``a..b`` (inclusive) into a dump directory — the round-granular
``profile_dir`` knob traces the WHOLE loop, gigabytes on a long run —
and blocks on the last bracketed step's output before stopping, so its
device-side activity lands in the dump. The round log then prints the
attribution of those steps (telemetry/traceparse.py).

Device tracer only: with the host tracer on, the runtime writes one
event per inner call of the host-side re-tiling of every batch it
copies to the device (4.6 M events, 1.85 s a batch against
milliseconds: PERF.md, PR 23), and Python frames evict the op events
from the profiler's capped buffer.

The step describes itself: on ``update()`` the trainer registers
(weakly; nothing is lowered) how to lower the step it ran, and
:func:`step_hlo_text` / :func:`step_scope_table` lower, compile (served
from the compile caches) and memoise on demand — a reader of a dump
needs no handle on the ``Trainer``.
"""

from __future__ import annotations

import contextlib
import re
import time
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

from .trace import TRACER

_RANGE_RE = re.compile(r"^\s*(\d+)\s*-\s*(\d+)\s*$")

# -- the step's own description ----------------------------------------------

_step: Dict[str, Any] = {"lower": None, "text": None, "scopes": None}


def register_step(lower: Callable[[], Any]) -> None:
    """``lower``: a bound method that returns the ``jax.stages.Lowered``
    of the train step its trainer last ran. Held weakly; forgets what
    was memoised for an earlier step."""
    _step.update(lower=weakref.WeakMethod(lower), text=None, scopes=None)


def step_hlo_text() -> Optional[str]:
    """The compiled text of the registered train step (instruction
    names as a device trace shows them, ``op_name`` metadata included),
    or ``None`` when no trainer has run a step (or it is gone)."""
    if _step["text"] is None:
        lower = _step["lower"]() if _step["lower"] is not None else None
        if lower is None:
            return None
        _step["text"] = lower().compile().as_text()
    return _step["text"]


def step_scope_table() -> Dict[str, str]:
    """``traceparse.scope_table`` of :func:`step_hlo_text`, memoised;
    empty when there is no step to describe."""
    if _step["scopes"] is None:
        from .traceparse import scope_table
        text = step_hlo_text()
        _step["scopes"] = scope_table(text) if text else {}
    return _step["scopes"]


# -- brackets ---------------------------------------------------------------


@contextlib.contextmanager
def device_trace(log_dir: str):
    """A ``jax.profiler`` bracket with the device tracer only (host and
    Python tracers at level 0, see the module docstring). Yields the
    ``(time.time_ns(), time.perf_counter())`` pair taken at start,
    through which ``traceparse.attribute_profile`` puts the ``train.*``
    spans on the dump's clock."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = opts.host_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield time.time_ns(), time.perf_counter()
    finally:
        jax.profiler.stop_trace()


def parse_step_range(spec: str) -> Tuple[int, int]:
    """``"a-b"`` -> (a, b) with 0 <= a <= b; a bare ``"n"`` means one
    step (n, n)."""
    spec = spec.strip()
    m = _RANGE_RE.match(spec)
    if m:
        a, b = int(m.group(1)), int(m.group(2))
    elif spec.isdigit():
        a = b = int(spec)
    else:
        raise ValueError(
            f"telemetry_profile_steps must be 'a-b' or 'n', got {spec!r}")
    if a > b:
        raise ValueError(
            f"telemetry_profile_steps: start {a} > stop {b}")
    return a, b


class StepProfiler:
    """Drive from the train loop: ``maybe_start(step)`` before the
    dispatch of global step ``step``, ``maybe_stop(step_after, ready)``
    after it (with the count already advanced). Idempotent and safe to
    leave in the loop — outside the bracket both calls are integer
    compares. ``close()`` finalizes a bracket the loop never exited
    (e.g. the run ended inside it)."""

    def __init__(self, spec: str, dump_dir: str):
        self.start_step, self.stop_step = parse_step_range(spec)
        self.dump_dir = dump_dir
        self.active = False
        self.done = False
        self._bracket = None
        self._clock: Optional[Tuple[int, float]] = None

    def maybe_start(self, step: int) -> None:
        if self.done or self.active or step < self.start_step:
            return
        self._bracket = device_trace(self.dump_dir)
        self._clock = self._bracket.__enter__()
        self.active = True
        TRACER.instant("profiler.start_trace", cat="profile",
                       args={"step": step, "dir": self.dump_dir})

    def maybe_stop(self, next_step: int, ready: Any = None) -> None:
        """``next_step`` is the step count AFTER the last dispatch; the
        bracket closes once it passes ``stop_step``."""
        if not self.active or next_step <= self.stop_step:
            return
        self._stop(ready)

    def _stop(self, ready: Any = None) -> None:
        import jax
        if ready is not None:
            try:
                jax.block_until_ready(ready)
            except Exception:
                pass
        self._bracket.__exit__(None, None, None)
        self._bracket = None
        self.active = False
        self.done = True
        TRACER.instant("profiler.stop_trace", cat="profile",
                       args={"dir": self.dump_dir})

    def close(self, ready: Any = None) -> None:
        if self.active:
            self._stop(ready)

    def summarize(self) -> Optional[dict]:
        """``traceparse.attribute_profile`` of the bracketed steps —
        None until the bracket has closed, and where the dump has
        nothing to read (the CPU backend has no device plane). The
        driver prints ``attribution_fragment`` of this after the
        bracket closes."""
        if not self.done:
            return None
        from .traceparse import attribute_profile
        try:
            return attribute_profile(self.dump_dir, clock=self._clock)
        except Exception:
            return None
