"""cxxnet_tpu.telemetry — unified observability for training and serving.

One registry, one tracer, every subsystem a client:

* :mod:`.registry` — process-wide thread-safe Counter / Gauge /
  log-bucketed Histogram registry (:data:`REGISTRY`). ``resilience.
  counters``, ``serve.ServingStats``, the IO prefetch queue and the
  checkpoint layer all store their numbers HERE; ``/statz`` and
  ``/metrics`` are views of it.
* :mod:`.trace` — bounded-ring span tracing (:data:`TRACER`), exported
  as perfetto-loadable Chrome trace JSON via ``telemetry_trace=path``.
  A ``task = train`` run keeps its own ``cat="train"`` spans
  (``train.data_wait`` / ``h2d_stage`` / ``step_dispatch`` /
  ``metric_drain`` / ``device_block``) in that ring WITHOUT the knob,
  and its ``cat="setup"`` spans (``setup.task`` / ``setup.weights`` /
  ``setup.input``, ``train.round``, and ``compile.trace`` /
  ``compile.lower`` / ``compile.backend`` from the compile instrument,
  :func:`.anomaly.install_compile_counter`), governed by
  ``telemetry_steptime`` (default 1; 0 keeps nothing): about half a
  microsecond a span, and there after the session closed.
* :mod:`.steptime` — :class:`StepTimeProbe`, the amortized-sync
  data-wait / dispatch / drain / device breakdown with the input-bound
  vs compute-bound verdict in the round log (``dispatch_ms`` is the
  enqueue alone; ``drain_ms`` the wait in the train-metric drain).
* :mod:`.exporter` — Prometheus text rendering, the standalone
  ``telemetry_port`` scrape endpoint, and the ``telemetry_log`` JSONL
  event log.
* :mod:`.profiler` — ``telemetry_profile_steps=a-b`` jax.profiler
  brackets (device tracer only), after which the round log prints the
  steps' phase x fused-kind x layer table and the longest idle gaps by
  ``train.*`` span; and the step's own description
  (``step_hlo_text`` / ``step_scope_table``).
* :mod:`.traceparse` — the reader: ``scope_table`` / ``classify`` /
  ``attribute_profile`` over the scopes the program puts on every
  device op (a layer's name, ``optimizer``, ``fused.<kind>``).

:class:`TelemetrySession` bundles the knob-driven pieces so the task
driver (main.py) owns exactly one object with one ``close()``.
"""

from __future__ import annotations

import os
from typing import Optional

from .registry import REGISTRY, MetricRegistry, get_registry, log_buckets
from .trace import TRACER, Tracer, get_tracer
from .disttrace import (DISTTRACE, DistTracer, TraceContext,
                        estimate_offset, get_disttracer,
                        parse_traceparent, set_trace_identity)
from .steptime import StepTimeProbe
from .exporter import (PROMETHEUS_CONTENT_TYPE, MetricsServer,
                       TelemetryLogger, render_prometheus)
from .profiler import StepProfiler
from .ledger import (LEDGER, RunLedger, config_hash, get_ledger,
                     new_run_id, read_ledger, run_info, set_run_info)
from .aggregate import (FleetAggregator, FleetView, SnapshotPusher,
                        export_snapshot, merge_snapshots, quantile,
                        render_fleet)
from .anomaly import (HangWatchdog, RecompileStormDetector,
                      StragglerDetector, install_compile_counter)
from .slo import SLOTracker

__all__ = [
    "REGISTRY", "MetricRegistry", "get_registry", "log_buckets",
    "TRACER", "Tracer", "get_tracer",
    "DISTTRACE", "DistTracer", "TraceContext", "estimate_offset",
    "get_disttracer", "parse_traceparent", "set_trace_identity",
    "StepTimeProbe", "StepProfiler",
    "MetricsServer", "TelemetryLogger", "render_prometheus",
    "PROMETHEUS_CONTENT_TYPE", "TelemetrySession",
    "LEDGER", "RunLedger", "get_ledger", "new_run_id", "config_hash",
    "set_run_info", "run_info", "read_ledger",
    "FleetAggregator", "FleetView", "SnapshotPusher", "export_snapshot",
    "merge_snapshots", "quantile", "render_fleet",
    "HangWatchdog", "RecompileStormDetector", "StragglerDetector",
    "install_compile_counter", "SLOTracker",
]


class TelemetrySession:
    """Everything the ``telemetry_*`` config knobs turn on, with one
    close(). Built by main.py from a :class:`cxxnet_tpu.config.
    TelemetryConfig`; every piece is optional and absent by default, so
    an unconfigured run pays only the disabled-tracer attribute checks.
    """

    def __init__(self, cfg, silent: bool = False,
                 cfg_hash: str = "", host: int = 0):
        self.cfg = cfg
        self.silent = silent
        self.host = int(host)
        self.logger: Optional[TelemetryLogger] = None
        self.server: Optional[MetricsServer] = None
        self.profiler: Optional[StepProfiler] = None
        self.pusher: Optional[SnapshotPusher] = None
        self.aggregator: Optional[FleetAggregator] = None
        self.straggler: Optional[StragglerDetector] = None
        self.watchdog: Optional[HangWatchdog] = None
        self.storm: Optional[RecompileStormDetector] = None
        # the aggregating host's most recent windowed straggler
        # verdicts — the elastic demotion advisory reads them at round
        # boundaries (elastic/preempt.DemotionAdvisor)
        self.last_straggler_verdicts: list = []
        # run identity: explicit knob > env (so N processes of one run
        # launched by a driver share one id) > fresh
        self.run_id = (cfg.run_id or os.environ.get("CXXNET_RUN_ID")
                       or new_run_id())
        self.cfg_hash = cfg_hash
        set_run_info(self.run_id, cfg_hash)
        if cfg.ledger_path:
            LEDGER.enable(cfg.ledger_path, self.run_id, host=self.host)
        if cfg.ledger_path or cfg.fleet_dir:
            # compile events (the task driver's compile instrument,
            # anomaly.install_compile_counter) feed the storm detector
            self.storm = RecompileStormDetector(
                window_s=cfg.storm_window_s,
                threshold=cfg.storm_threshold)
        # the train loop's own spans and the set-up's (setup.*,
        # train.round, compile.*) stay in the tracer's ring without
        # telemetry_trace (telemetry/trace.py "Two levels"): the same
        # knob that governs the step-time probe governs them
        TRACER.keep(("train", "setup") if cfg.steptime else ())
        if cfg.trace_path:
            TRACER.enable(capacity=cfg.trace_capacity)
            # the distributed layer rides the same knob: cross-process
            # context propagation, legacy-span stamping, tail-exemplar
            # retention and clock anchors (doc/tasks.md "Distributed
            # tracing")
            DISTTRACE.enable(sample=cfg.trace_sample,
                             tail_pct=cfg.trace_tail_pct,
                             tail_window=cfg.trace_tail_window,
                             anchor_s=cfg.trace_anchor_s)
            set_trace_identity(host=self.host)
        if cfg.log_path:
            self.logger = TelemetryLogger(
                cfg.log_path, interval_s=cfg.log_interval_s,
                max_bytes=cfg.log_max_kb << 10).start()
        if cfg.port:
            try:
                self.server = MetricsServer(port=cfg.port).start()
            except OSError as e:
                # telemetry must never kill the run: a taken port (e.g.
                # several ranks sharing a host) degrades to no endpoint
                print(f"WARNING: telemetry_port {cfg.port} unavailable "
                      f"({e}); /metrics endpoint disabled", flush=True)
            else:
                if not silent:
                    print(f"telemetry: /metrics on "
                          f"http://127.0.0.1:{self.server.port}",
                          flush=True)
        if cfg.profile_steps:
            self.profiler = StepProfiler(cfg.profile_steps,
                                         cfg.profile_dir)
        if cfg.fleet_dir:
            # every worker pushes; host 0 additionally aggregates and
            # promotes its /metrics endpoint to the merged fleet view
            self.pusher = SnapshotPusher(
                cfg.fleet_dir, host=self.host,
                interval_s=cfg.push_interval_s,
                run_id=self.run_id).start()
            if self.host == 0:
                self.aggregator = FleetAggregator(cfg.fleet_dir,
                                                  host=self.host,
                                                  run_id=self.run_id)
                self.straggler = StragglerDetector(
                    factor=cfg.straggler_factor,
                    min_steps=cfg.straggler_min_steps)
                if self.server is not None:
                    self.server.render_fn = self.aggregator.render
        if cfg.hang_s > 0 or cfg.hang_dryrun:
            # progress = the steptime probe's step counter (default-on);
            # with telemetry_steptime=0 the watchdog never arms, which
            # is documented behavior, not a hang
            steps = REGISTRY.counter("cxxnet_steptime_steps_total")
            self.watchdog = HangWatchdog(
                cfg.hang_s if cfg.hang_s > 0 else 3600.0,
                progress_fn=lambda: steps.value)
            if cfg.hang_s > 0:
                self.watchdog.start()
            if cfg.hang_dryrun:
                # exercise the capture -> ledger path end to end
                # without counting a hang (tools/smoke_fleet.py)
                self.watchdog.dump_now(dry_run=True)

    def make_probe(self) -> StepTimeProbe:
        return StepTimeProbe(sync_interval=self.cfg.sync_interval)

    def round_tick(self, round_no: int, **fields) -> str:
        """End-of-round fleet housekeeping, called by the train loop:
        push this worker's snapshot, feed the recompile-storm detector,
        ledger the round boundary, and (aggregating host only) refresh
        the fleet view for straggler verdicts. Returns a round-log
        fragment ("" when there is nothing fleet-worthy to say)."""
        LEDGER.event("round_end", round=round_no, **fields)
        if self.pusher is not None:
            self.pusher.push_now()
        if self.storm is not None:
            c = REGISTRY.get("cxxnet_compiles_total")
            if c is not None:
                self.storm.observe(c.value)
        if self.aggregator is None or self.straggler is None:
            return ""
        view = self.aggregator.view()
        verdicts = self.straggler.check(view, round_no)
        self.last_straggler_verdicts = verdicts
        frag = ""
        if len(view.hosts) > 1:
            meds = []
            for h in view.hosts:
                for vals, v in view.host_samples(
                        "cxxnet_steptime_step_seconds", h):
                    if isinstance(v, dict) and vals == () and v["count"]:
                        meds.append("h%d=%.1f" % (h, 1e3 * quantile(
                            v["buckets"], v["counts"], 0.5)))
            if meds:
                frag += "\tfleet_p50_ms:" + ",".join(meds)
        frag += StragglerDetector.fragment(verdicts)
        return frag

    def close(self, ready=None, status: str = "ok") -> None:
        """Finalize in dependency order: close a live profiler bracket,
        stop the watchdog, final fleet push, run_end to the ledger,
        flush the JSONL log, dump the trace, stop the scrape server."""
        if self.profiler is not None:
            self.profiler.close(ready)
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.pusher is not None:
            self.pusher.stop()
        LEDGER.event("run_end", status=status)
        if self.logger is not None:
            self.logger.stop()
        if self.cfg.trace_path:
            # final wall-clock anchor so the very last spans are dated
            DISTTRACE.anchor(force=True)
            n = TRACER.dump(self.cfg.trace_path)
            if not self.silent:
                print(f"telemetry: {n} trace events -> "
                      f"{self.cfg.trace_path}"
                      + (f" ({TRACER.dropped} dropped)"
                         if TRACER.dropped else ""), flush=True)
        if self.server is not None:
            self.server.stop()
