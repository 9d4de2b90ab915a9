"""Training step-time breakdown: data-wait vs dispatch vs device time.

THE question for a TPU trainer — is the step input-bound or
compute-bound? — cannot be answered from wall clock alone, because JAX
dispatch is asynchronous: ``update()`` returns as soon as the step is
enqueued, so host-side timing sees only (data-wait + dispatch) while the
device runs behind. Reading any step output syncs host to device and
would serialize the very overlap the prefetch pipeline exists for, so
this probe uses the same amortization trick as ``sentinel_interval``:
it blocks on the step's ready future (the loss) only every
``sync_interval`` steps, attributing the measured block time to the
device. Steady state therefore costs <= 1 host sync per
``sync_interval`` steps (asserted by tests and tools/smoke_telemetry.py)
and ZERO extra syncs when the interval is larger than the round.

Per-step components:

* **data_wait** — host blocked pulling the next batch from the input
  pipeline (iterator + prefetch queue). Large => input-bound: buy
  decode threads / prefetch depth, not more chips.
* **dispatch** — host time enqueueing the step inside the update call
  (staging, tracing the first call, the jitted call itself). Large
  next to the device time (a small step) => use ``train_chain``.
* **drain** — host time the update call spent fetching the PREVIOUS
  step's train-metric outputs (``eval_train``, the default): a wait
  for the device that lasts about a device step once the host runs
  ahead, whatever the enqueue costs. Reported apart (``drain_ms``) so
  that ``dispatch_ms`` tracks the trainer and not the device.
* **device_block** — how far the device lags the host when the probe
  syncs, i.e. device compute the host did NOT hide behind its own work.
  Large => compute-bound: the chip is the bottleneck.

Rolling EMAs smooth scheduler noise; :meth:`verdict` compares the
data-wait and device-block EMAs and labels the run ``input-bound``,
``compute-bound``, or ``balanced`` — emitted into the round log by
main.py and exported as gauges through the registry.
"""

from __future__ import annotations

import time
from typing import Any, Optional

from .registry import REGISTRY, MetricRegistry
from .trace import TRACER


class StepTimeProbe:
    """Feed with per-step host timings; it syncs sparsely and keeps the
    breakdown EMAs. Not thread-safe — it belongs to the (single) train
    loop thread."""

    def __init__(self, sync_interval: int = 8, ema_alpha: float = 0.3,
                 registry: Optional[MetricRegistry] = None,
                 tracer=None):
        self.sync_interval = max(1, int(sync_interval))
        self.ema_alpha = float(ema_alpha)
        self.steps = 0
        self.syncs = 0
        # per-step EMAs (seconds); None until the first sync window closes
        self.data_wait_ema: Optional[float] = None
        self.dispatch_ema: Optional[float] = None
        self.drain_ema: Optional[float] = None
        self.device_block_ema: Optional[float] = None
        self.step_wall_ema: Optional[float] = None
        self._win_data_wait = 0.0
        self._win_dispatch = 0.0
        self._win_drain = 0.0
        self._win_steps = 0
        self._win_t0: Optional[float] = None
        self._pending_data_wait = 0.0
        self._tracer = tracer or TRACER
        reg = registry or REGISTRY
        g = lambda n, h: reg.gauge(n, h)
        self._g_data = g("cxxnet_steptime_data_wait_seconds",
                         "EMA of per-step host time blocked on input")
        self._g_disp = g("cxxnet_steptime_dispatch_seconds",
                         "EMA of per-step host time dispatching the step")
        self._g_dev = g("cxxnet_steptime_device_block_seconds",
                        "EMA of per-step device time the host waited out "
                        "at sync points")
        self._g_wall = g("cxxnet_steptime_step_wall_seconds",
                         "EMA of per-step wall time")
        # per-step wall-time DISTRIBUTION (not just the EMA): the fleet
        # layer merges these bucket-wise across hosts and the straggler
        # rule compares host median vs fleet median (telemetry.anomaly)
        self._h_step = reg.histogram(
            "cxxnet_steptime_step_seconds",
            "Per-step wall time (window-averaged at each sync point)")
        self._c_sync = reg.counter(
            "cxxnet_steptime_syncs_total",
            "Blocking host-device syncs taken by the step-time probe")
        self._c_steps = reg.counter(
            "cxxnet_steptime_steps_total",
            "Train steps observed by the step-time probe")

    # -- feeding ---------------------------------------------------------
    def note_data_wait(self, seconds: float) -> None:
        """Bank the input-fetch time for the NEXT record_step call (the
        loop pulls the batch before it dispatches)."""
        self._pending_data_wait += max(0.0, seconds)

    def record_step(self, dispatch_s: float, ready: Any = None,
                    steps: int = 1, drain_s: float = 0.0) -> None:
        """One dispatched update (or a ``steps``-long fused chain):
        ``dispatch_s`` the enqueue, ``drain_s`` what the same call then
        spent in the train-metric drain. ``ready`` is any device value
        produced by the step (the loss) — blocked on only at sync
        points, never per step."""
        now = time.perf_counter()
        if self._win_t0 is None:
            self._win_t0 = now - dispatch_s - drain_s \
                - self._pending_data_wait
        self.steps += steps
        self._c_steps.inc(steps)
        self._win_steps += steps
        self._win_data_wait += self._pending_data_wait
        self._win_dispatch += max(0.0, dispatch_s)
        self._win_drain += max(0.0, drain_s)
        self._pending_data_wait = 0.0
        if self._win_steps < self.sync_interval:
            return
        # sync point: block on the step's output and charge the wait to
        # the device
        block = 0.0
        if ready is not None:
            t0 = time.perf_counter()
            try:
                if hasattr(ready, "block_until_ready"):
                    ready.block_until_ready()      # jax.Array fast path
                else:
                    import jax
                    jax.block_until_ready(ready)
            except Exception:
                pass
            block = time.perf_counter() - t0
            self.syncs += 1
            self._c_sync.inc()
            self._tracer.add_complete("train.device_block", t0,
                                      t0 + block,
                                      cat="train",
                                      args={"steps": self._win_steps})
        self._close_window(block)

    def _close_window(self, block_s: float) -> None:
        n = self._win_steps
        if n <= 0:
            return
        wall = max(time.perf_counter() - (self._win_t0 or 0.0), 0.0)
        # one histogram observation PER STEP at the window's average —
        # step counts stay comparable across hosts with different sync
        # intervals, which the fleet median comparison depends on
        per_step = wall / n
        for _ in range(n):
            self._h_step.observe(per_step)
        a = self.ema_alpha
        mix = lambda old, new: new if old is None else old + a * (new - old)
        self.data_wait_ema = mix(self.data_wait_ema,
                                 self._win_data_wait / n)
        self.dispatch_ema = mix(self.dispatch_ema, self._win_dispatch / n)
        self.drain_ema = mix(self.drain_ema, self._win_drain / n)
        self.device_block_ema = mix(self.device_block_ema, block_s / n)
        self.step_wall_ema = mix(self.step_wall_ema, wall / n)
        self._g_data.set(self.data_wait_ema)
        self._g_disp.set(self.dispatch_ema)
        self._g_dev.set(self.device_block_ema)
        self._g_wall.set(self.step_wall_ema)
        self._win_data_wait = 0.0
        self._win_dispatch = 0.0
        self._win_drain = 0.0
        self._win_steps = 0
        self._win_t0 = None

    # -- reading ---------------------------------------------------------
    def verdict(self) -> str:
        """``input-bound`` / ``compute-bound`` / ``balanced`` — or
        ``warming-up`` before the first sync window closes. The 1.2x
        hysteresis band keeps the label stable when the two sides are
        within scheduler noise of each other."""
        dw, dev = self.data_wait_ema, self.device_block_ema
        if dw is None or dev is None:
            return "warming-up"
        # a verdict needs a material signal: the winning side must be at
        # least 5% of the step wall, or the step is dominated by neither
        # (e.g. dispatch/compile overhead) and the honest label is
        # "balanced"
        floor = 0.05 * (self.step_wall_ema or 0.0)
        if dw > dev * 1.2 and dw > floor:
            return "input-bound"
        if dev > dw * 1.2 and dev > floor:
            return "compute-bound"
        return "balanced"

    def report_fragment(self) -> str:
        """Round-log fragment, same ``\\tkey:value`` dialect as the
        metric line: per-step ms for each component plus the verdict."""
        if self.data_wait_ema is None:
            return ""
        ms = lambda v: (v or 0.0) * 1e3
        return ("\tdata_ms:%.2f\tdispatch_ms:%.2f\tdrain_ms:%.2f"
                "\tdevice_ms:%.2f\tbound:%s" % (
                    ms(self.data_wait_ema), ms(self.dispatch_ema),
                    ms(self.drain_ema), ms(self.device_block_ema),
                    self.verdict()))
