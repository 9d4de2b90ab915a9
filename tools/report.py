#!/usr/bin/env python
"""Run-report generator: ledger + telemetry JSONL + bench trajectory -> md.

Gives training/serving runs the same artifact discipline the bench has:
one markdown file a human (or the next session) reads to answer "what
happened to this run" without grepping logs —

  * identity & topology (run_start), outcome (run_end status);
  * round trajectory (round_end events: images/sec, loss, seconds);
  * incident timeline: sentinel trips, rollbacks, breaker transitions,
    hang dumps (stack excerpt), stragglers, recompile storms;
  * serving timeline: fleet bring-up, hot weight reloads (old/new
    round + digest), replica lifecycle transitions;
  * topology timeline: elastic joins/leaves, generation bumps with
    membership/leader/dp width, topology-change resumes, demotion
    advisories (doc/elastic_runbook.md);
  * checkpoint activity (saves/loads, failures, IO seconds);
  * step-time + fleet metrics from the LAST telemetry_log snapshot
    (EMAs, per-host straggler ratios, hang/compile counters);
  * serve SLO attainment & burn rate when the run served traffic;
  * the BENCH_r*.json trajectory, so run context and perf history land
    in one place.

Ledger reads are open-world (telemetry.ledger.iter_ledger): unknown
event types render in the timeline as-is, malformed lines are skipped.

Usage:
  python tools/report.py --ledger run.ledger.jsonl \
      [--telemetry-log tel.jsonl] [--bench 'BENCH_r*.json' ...] \
      [-o REPORT.md]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from collections import Counter
from typing import Any, Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _ts(t: Optional[float]) -> str:
    if not t:
        return "?"
    return time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime(t)) + "Z"


def _fmt(v: Any) -> str:
    if isinstance(v, float):
        return "%.4g" % v
    return str(v)


def load_ledger(path: str) -> List[Dict[str, Any]]:
    from cxxnet_tpu.telemetry.ledger import iter_ledger
    return list(iter_ledger(path))


def load_last_snapshot(path: str) -> Optional[Dict[str, Any]]:
    """Last parseable line of a telemetry_log JSONL (+ its .1 rotation
    predecessor is irrelevant — the newest line wins)."""
    last = None
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "metrics" in rec:
                    last = rec
    except OSError:
        return None
    return last


# -- sections -----------------------------------------------------------------

def section_identity(events: List[Dict], out: List[str]) -> None:
    starts = [e for e in events if e.get("event") == "run_start"]
    ends = [e for e in events if e.get("event") == "run_end"]
    run_id = (starts or events or [{}])[0].get("run_id", "?")
    out.append("# Run report — `%s`" % run_id)
    out.append("")
    if starts:
        s = starts[0]
        mesh = s.get("mesh") or {}
        out.append("| field | value |")
        out.append("|---|---|")
        out.append("| started | %s |" % _ts(s.get("ts")))
        out.append("| task | %s |" % s.get("task", "?"))
        out.append("| config hash | `%s` |" % s.get("config_hash", "?"))
        out.append("| platform | %s |" % s.get("platform", "?"))
        out.append("| processes | %s |" % s.get("process_count", "?"))
        out.append("| devices/process | %s |" % s.get("devices", "?"))
        if mesh:
            out.append("| mesh (data/seq/pipe/model) | %s/%s/%s/%s |" % (
                mesh.get("data", 1), mesh.get("seq", 1),
                mesh.get("pipe", 1), mesh.get("model", 1)))
        hosts = sorted({e.get("host", 0) for e in events})
        out.append("| hosts seen in ledger | %s |" %
                   ",".join(str(h) for h in hosts))
    if ends:
        e = ends[-1]
        out.append("| ended | %s (status: **%s**) |"
                   % (_ts(e.get("ts")), e.get("status", "?")))
    elif starts:
        out.append("| ended | *no run_end event — crashed or still "
                   "running* |")
    out.append("")


def section_rounds(events: List[Dict], out: List[str]) -> None:
    rounds = [e for e in events if e.get("event") == "round_end"
              and e.get("host", 0) == 0]
    if not rounds:
        return
    out.append("## Round trajectory (host 0)")
    out.append("")
    out.append("| round | images | images/sec | seconds | loss |")
    out.append("|---|---|---|---|---|")
    shown = rounds if len(rounds) <= 30 else \
        rounds[:10] + [None] + rounds[-19:]
    for e in shown:
        if e is None:
            out.append("| ... | | | | |")
            continue
        out.append("| %s | %s | %s | %s | %s |" % (
            e.get("round", "?"), e.get("images", ""),
            _fmt(e.get("images_per_sec", "")), _fmt(e.get("seconds", "")),
            _fmt(e.get("loss", ""))))
    out.append("")


_INCIDENT_EVENTS = ("sentinel_trip", "rollback", "breaker_transition",
                    "hang_dump", "straggler", "recompile_storm")

# events tools/replay.py can time-travel back into; the --incident N
# address is the row's index among THESE events in file order (must
# match cxxnet_tpu.replay.reconstruct.list_incidents)
try:
    from cxxnet_tpu.replay.reconstruct import \
        INCIDENT_EVENTS as _REPLAYABLE_EVENTS
except Exception:                                # report must render
    _REPLAYABLE_EVENTS = ("sentinel_trip", "rollback",
                          "deploy_incident", "dataservice_degrade",
                          "straggler")


def section_incidents(events: List[Dict], out: List[str],
                      ledger_path: str = "") -> None:
    counts = Counter(e.get("event") for e in events)
    out.append("## Event summary")
    out.append("")
    out.append("| event | count |")
    out.append("|---|---|")
    for name, n in sorted(counts.items()):
        out.append("| %s | %d |" % (name, n))
    out.append("")
    incidents = [e for e in events if e.get("event") not in
                 ("round_end", "compile", "ckpt_save", "ckpt_load",
                  "run_start", "run_end",
                  # serving lifecycle renders in its own timeline;
                  # LM-serving events are routine lifecycle too (a
                  # deadline/cancel eviction is the protocol working,
                  # not an incident)
                  "serve_start", "weights_reload", "replica_state",
                  "lm_serve_start", "kv_evict", "prefill_handoff",
                  # elastic lifecycle renders in the topology timeline
                  "elastic_join", "elastic_leave", "topology_change",
                  "elastic_resume", "elastic_advice",
                  # model-health trail renders in its own section
                  "model_health", "health_advice",
                  # deployment lifecycle renders in its own timeline;
                  # deploy_incident stays HERE — a gated rejection is
                  # an incident, wherever it is also narrated
                  "deploy_promote", "deploy_rollback")]
    if not incidents:
        out.append("No incidents recorded — clean run.")
        out.append("")
        return
    out.append("## Incident timeline")
    out.append("")
    # --incident N addressing for the replay hint under each row
    replay_idx = {id(e): i for i, e in enumerate(
        e2 for e2 in events
        if e2.get("event") in _REPLAYABLE_EVENTS)}
    for e in incidents[:100]:
        etype = e.get("event")
        host = e.get("host", 0)
        line = "- %s `h%s` **%s**" % (_ts(e.get("ts")), host, etype)
        if etype == "sentinel_trip":
            line += ": %s" % e.get("reason", "?")
        elif etype == "rollback":
            line += ": round %s -> %s (lr_scale %s)" % (
                e.get("round", "?"), e.get("to_round", "?"),
                _fmt(e.get("lr_scale", "?")))
            if e.get("provenance"):
                line += " — `%s`" % e["provenance"]
        elif etype == "breaker_transition":
            line += ": %s -> %s" % (e.get("from_state", "?"),
                                    e.get("to_state", "?"))
        elif etype == "straggler":
            line += ": host %s at %sx fleet median (%ss vs %ss)" % (
                e.get("straggler_host", e.get("host")),
                e.get("ratio", "?"),
                _fmt(e.get("median_s", "?")),
                _fmt(e.get("fleet_median_s", "?")))
        elif etype == "recompile_storm":
            line += ": %s compiles in %ss window" % (
                e.get("compiles_in_window", "?"), e.get("window_s", "?"))
        elif etype == "hang_dump":
            line += ": stalled %ss%s" % (
                e.get("stalled_for_s", "?"),
                " (dry run)" if e.get("dry_run") else "")
        else:
            extra = {k: v for k, v in e.items()
                     if k not in ("schema", "ts", "run_id", "host",
                                  "event", "trace_id")}
            if extra:
                line += ": " + _fmt(extra)
        # a row stamped with a distributed-trace id names the exact
        # span tree to pull up in the assembled fleet trace
        if e.get("trace_id"):
            line += " — trace `%s`" % e["trace_id"]
        out.append(line)
        if id(e) in replay_idx:
            out.append("  - replay with: `python tools/replay.py %s "
                       "--incident %d`" % (ledger_path or "<ledger>",
                                           replay_idx[id(e)]))
        if etype == "hang_dump" and e.get("stacks"):
            first = str(e["stacks"]).strip().splitlines()
            out.append("")
            out.append("  ```")
            out.extend("  " + l for l in first[:12])
            if len(first) > 12:
                out.append("  ... (%d more lines in ledger)"
                           % (len(first) - 12))
            out.append("  ```")
    out.append("")


def section_modelhealth(events: List[Dict], out: List[str]) -> None:
    """Model health: the per-round ``model_health`` stat trail, every
    windowed-detector ``health_advice``, and each rollback's NaN
    provenance — the "which layer and why" view next to the incident
    timeline (doc/tasks.md "Model health")."""
    mh = [e for e in events if e.get("event") == "model_health"]
    advice = [e for e in events if e.get("event") == "health_advice"]
    prov = [e for e in events
            if e.get("event") in ("sentinel_trip", "rollback")
            and e.get("provenance")]
    if not mh and not advice and not prov:
        return
    out.append("## Model health")
    out.append("")
    if prov:
        out.append("NaN provenance (first non-finite site per "
                   "anomaly):")
        out.append("")
        for e in prov:
            out.append("- %s `h%s` **%s** round %s: `%s`" % (
                _ts(e.get("ts")), e.get("host", 0), e.get("event"),
                e.get("round", "?"), e.get("provenance")))
        out.append("")
    if advice:
        out.append("Training-dynamics advice (windowed detectors, "
                   "deduped per onset):")
        out.append("")
        for e in advice[:100]:
            line = "- %s `h%s` **%s** on `%s` (value %s" % (
                _ts(e.get("ts")), e.get("host", 0), e.get("kind", "?"),
                e.get("layer", "?"), _fmt(e.get("value", "?")))
            if e.get("round") is not None:
                line += ", round %s" % e.get("round")
            if e.get("provenance"):
                line += ", `%s`" % e["provenance"]
            out.append(line + ")")
        out.append("")
    if mh:
        out.append("| round | grad norm | dead max | BN var min | "
                   "update ratio max | act abs-max | loss scale |")
        out.append("|---|---|---|---|---|---|---|")
        shown = mh if len(mh) <= 30 else mh[:10] + [None] + mh[-19:]
        for e in shown:
            if e is None:
                out.append("| ... | | | | | | |")
                continue

            def pair(field):
                v = e.get(field)
                if v is None:
                    return ""
                lay = e.get(field + "_layer")
                return "%s (%s)" % (_fmt(v), lay) if lay else _fmt(v)
            out.append("| %s | %s | %s | %s | %s | %s | %s |" % (
                e.get("round", "?"), _fmt(e.get("grad_norm", "")),
                pair("dead_max"), pair("bn_var_min"),
                pair("update_ratio_max"), pair("act_absmax"),
                _fmt(e.get("loss_scale", ""))))
        out.append("")
        last = mh[-1]
        if last.get("overflows"):
            out.append("%s fp16 scaler-overflow step(s) observed at "
                       "health syncs." % last["overflows"])
            out.append("")


_SERVE_EVENTS = ("serve_start", "weights_reload", "replica_state")


def section_serving(events: List[Dict], out: List[str]) -> None:
    """Serving timeline: fleet bring-up, hot weight reloads, replica
    lifecycle — rendered next to the training incident timeline so "the
    canary went degraded right after the r0012 reload" reads off one
    page."""
    serving = [e for e in events if e.get("event") in _SERVE_EVENTS]
    if not serving:
        return
    out.append("## Serving timeline")
    out.append("")
    for e in serving[:200]:
        etype = e.get("event")
        line = "- %s `h%s` **%s**" % (_ts(e.get("ts")),
                                      e.get("host", 0), etype)
        if etype == "serve_start":
            line += ": %s replica(s) on port %s" % (
                e.get("replicas", "?"), e.get("port", "?"))
            if e.get("versions"):
                line += ", versions %s" % e["versions"]
            if e.get("reload_s"):
                line += ", hot reload every %ss" % e["reload_s"]
        elif etype == "weights_reload":
            line += ": replica %s r%s -> r%s (digest `%s`%s)" % (
                e.get("replica", "?"), e.get("old_round", "?"),
                e.get("new_round", "?"), e.get("digest", "?"),
                ", canary" if e.get("canary") else "")
        elif etype == "replica_state":
            line += ": replica %s %s -> %s (%s)" % (
                e.get("replica", "?"), e.get("from_state", "?"),
                e.get("to_state", "?"), e.get("version", "?"))
        out.append(line)
    out.append("")
    # reload summary: how many swaps, which versions were served
    reloads = [e for e in serving if e.get("event") == "weights_reload"]
    if reloads:
        versions = sorted({("r%04d" % e["new_round"]) for e in reloads
                           if isinstance(e.get("new_round"), int)})
        out.append("%d replica weight swap(s); versions served: %s"
                   % (len(reloads), ", ".join(versions) or "?"))
        out.append("")


_DEPLOY_EVENTS = ("deploy_promote", "deploy_rollback",
                  "deploy_incident")


def section_deployments(events: List[Dict], out: List[str]) -> None:
    """Deployment timeline: every gated canary verdict — promotions
    with their evidence trail, rollbacks with the vetoing gate, and
    the incident record a rejection leaves (which ALSO appears in the
    incident timeline: a blocked checkpoint is an incident)."""
    deploys = [e for e in events if e.get("event") in _DEPLOY_EVENTS]
    if not deploys:
        return
    out.append("## Deployments")
    out.append("")
    for e in deploys[:200]:
        etype = e.get("event")
        line = "- %s `h%s` **%s**" % (_ts(e.get("ts")),
                                      e.get("host", 0), etype)
        if etype == "deploy_promote":
            line += ": %s (digest `%s`) after %ss window%s — gates %s" \
                % (e.get("version", "?"), e.get("digest", "?"),
                   e.get("window_s", "?"),
                   " (SUSPECT-extended)" if e.get("suspect") else "",
                   ", ".join(e.get("gates", [])) or "?")
            if e.get("canary_requests"):
                line += "; canary served %s request(s), %s failed" % (
                    e["canary_requests"], e.get("canary_failed", 0))
        elif etype == "deploy_rollback":
            line += ": %s rolled back to r%s — **%s** gate vetoed" % (
                e.get("version", "?"), e.get("incumbent_round", "?"),
                e.get("gate", "?"))
        elif etype == "deploy_incident":
            line += ": round %s (digest `%s`) rejected by **%s** gate" \
                % (e.get("round", "?"), e.get("digest", "?"),
                   e.get("gate", "?"))
            if e.get("layers"):
                line += ", layers %s" % ",".join(e["layers"])
            if e.get("reason"):
                line += " — %s" % e["reason"]
            if e.get("trace_ids"):
                line += " (traces: %s)" % ", ".join(
                    "`%s`" % t for t in e["trace_ids"][:4])
        out.append(line)
    out.append("")
    promos = sum(1 for e in deploys
                 if e.get("event") == "deploy_promote")
    rolls = sum(1 for e in deploys
                if e.get("event") == "deploy_rollback")
    blocked = sum(1 for e in deploys
                  if e.get("event") == "deploy_incident"
                  and not e.get("rolled_back"))
    out.append("%d promotion(s), %d rollback(s), %d blocked "
               "offline." % (promos, rolls, blocked))
    out.append("")


_QUANT_EVENTS = ("quant_calibrate", "cascade_escalate")


def section_quantization(events: List[Dict], out: List[str]) -> None:
    """Quantization line: PTQ calibration runs (which source round was
    derived, how many layers) plus a cascade-escalation rollup — the
    escalation rate IS the cost-per-request lever, so the report
    states it rather than making readers count events."""
    quant = [e for e in events if e.get("event") in _QUANT_EVENTS]
    if not quant:
        return
    out.append("## Quantization")
    out.append("")
    calibs = [e for e in quant if e.get("event") == "quant_calibrate"]
    for e in calibs[:20]:
        out.append("- %s `h%s` **quant_calibrate**: source round %s "
                   "(digest `%s`), %s layer(s) quantized, percentile "
                   "%s" % (_ts(e.get("ts")), e.get("host", 0),
                           e.get("source_round", "?"),
                           e.get("source_digest", "?"),
                           e.get("layers", "?"),
                           e.get("percentile", "?")))
    escs = [e for e in quant if e.get("event") == "cascade_escalate"]
    if escs:
        rows = sum(int(e.get("rows", 0)) for e in escs)
        total = sum(int(e.get("total", 0)) for e in escs)
        out.append("- cascade: %d escalation event(s), %d of %d rows "
                   "escalated to the flagship tier (%.1f%%)"
                   % (len(escs), rows, total,
                      100.0 * rows / max(1, total)))
    out.append("")


_ELASTIC_EVENTS = ("elastic_join", "elastic_leave", "topology_change",
                   "elastic_resume", "elastic_advice")


def section_topology(events: List[Dict], out: List[str]) -> None:
    """Topology timeline: who joined/left when, every generation bump
    with its membership/leader/width, every topology-change resume
    (round + dp width it restored onto), and straggler-demotion
    advisories — the ROADMAP-4 runbook's "what the ledger shows" view
    of an elastic run (doc/elastic_runbook.md)."""
    elastic = [e for e in events if e.get("event") in _ELASTIC_EVENTS]
    if not elastic:
        return
    out.append("## Topology timeline")
    out.append("")
    for e in elastic[:200]:
        etype = e.get("event")
        line = "- %s `h%s` **%s**" % (_ts(e.get("ts")),
                                      e.get("host", 0), etype)
        if etype == "elastic_join":
            line += ": worker %s (capacity %s, pid %s)" % (
                e.get("worker", "?"), e.get("capacity", "?"),
                e.get("pid", "?"))
        elif etype == "elastic_leave":
            line += ": worker %s (%s)" % (e.get("worker", "?"),
                                          e.get("reason", "?"))
        elif etype == "topology_change":
            line += ": gen %s (%s) members %s, leader %s, dp width %s" \
                % (e.get("gen", "?"), e.get("reason", "?"),
                   e.get("members", "?"), e.get("leader", "?"),
                   e.get("width", "?"))
        elif etype == "elastic_resume":
            line += ": round %s onto dp=%s (step_count %s%s)" % (
                e.get("round", "?"), e.get("dp", "?"),
                e.get("step_count", "?"),
                ", in-memory" if e.get("in_memory") else "")
        elif etype == "elastic_advice":
            line += ": %s worker %s (%sx fleet median)" % (
                e.get("action", "?"), e.get("worker", "?"),
                e.get("ratio", "?"))
        out.append(line)
    out.append("")
    gens = [e for e in elastic if e.get("event") == "topology_change"]
    if gens:
        widths = [str(e.get("width", "?")) for e in gens]
        out.append("%d generation(s); dp width trajectory: %s"
                   % (len(gens), " -> ".join(widths)))
        out.append("")


def section_checkpoints(events: List[Dict], out: List[str]) -> None:
    saves = [e for e in events if e.get("event") == "ckpt_save"]
    loads = [e for e in events if e.get("event") == "ckpt_load"]
    shard_writes = [e for e in events
                    if e.get("event") == "ckpt_shard_write"]
    if not saves and not loads and not shard_writes:
        return
    out.append("## Checkpoints")
    out.append("")
    for name, evs in (("saves", saves), ("loads", loads)):
        if not evs:
            continue
        bad = [e for e in evs if not e.get("ok", True)]
        secs = sum(float(e.get("seconds", 0) or 0) for e in evs)
        out.append("- %d %s (%d failed), %.2fs total IO"
                   % (len(evs), name, len(bad), secs))
    n_shard_saves = len([e for e in saves if e.get("format") == "shard"])
    if n_shard_saves:
        out.append("- %d save(s) wrote shard sets" % n_shard_saves)
    if shard_writes:
        mbs = [float(e.get("bytes", 0) or 0) / 1e6 for e in shard_writes]
        ms = [1e3 * float(e.get("seconds", 0) or 0) for e in shard_writes]
        out.append("- shard IO: %d shard file(s), %.1f MB total, "
                   "%.1f/%.1f ms avg/max per shard"
                   % (len(shard_writes), sum(mbs),
                      sum(ms) / len(ms), max(ms)))
    out.append("")


def section_telemetry(snap: Optional[Dict], out: List[str]) -> None:
    if not snap:
        return
    m = snap["metrics"]
    out.append("## Final telemetry snapshot")
    out.append("")
    out.append("(telemetry_log, uptime %ss)" % snap.get("uptime_s", "?"))
    out.append("")
    rows = []
    for key, label, scale in (
            ("cxxnet_steptime_step_wall_seconds", "step wall EMA (ms)", 1e3),
            ("cxxnet_steptime_data_wait_seconds", "data wait EMA (ms)", 1e3),
            ("cxxnet_steptime_device_block_seconds",
             "device block EMA (ms)", 1e3),
            ("cxxnet_steptime_steps_total", "steps", 1),
            ("cxxnet_compiles_total", "compiles", 1),
            ("cxxnet_hangs_total", "hangs detected", 1),
            ("cxxnet_recompile_storms_total", "recompile storms", 1),
            ("cxxnet_ledger_drops_total", "ledger drops", 1),
            # silent span loss must show while the run is alive, not
            # only in the dump's otherData.dropped_events post-mortem
            ("cxxnet_trace_dropped_total", "trace ring drops", 1),
            ("cxxnet_trace_tail_dropped_total",
             "trace tail-exemplar drops", 1),
            ("cxxnet_trace_spans_total", "distributed spans kept", 1)):
        v = m.get(key)
        if v is not None:
            rows.append("| %s | %s |" % (label, _fmt(v * scale)))
    strag = {k: v for k, v in m.items()
             if k.startswith("cxxnet_straggler_ratio")}
    for k, v in sorted(strag.items()):
        rows.append("| straggler ratio %s | %s |"
                    % (k.split("{", 1)[-1].rstrip("}"), _fmt(v)))
    if rows:
        out.append("| metric | value |")
        out.append("|---|---|")
        out.extend(rows)
    out.append("")
    # serve SLO attainment, when the snapshot saw serve traffic
    good = sum(v for k, v in m.items()
               if k.startswith("cxxnet_serve_slo_requests_total")
               and 'result="good"' in k)
    bad = sum(v for k, v in m.items()
              if k.startswith("cxxnet_serve_slo_requests_total")
              and 'result="bad"' in k)
    if good or bad:
        total = good + bad
        out.append("## Serve SLO")
        out.append("")
        out.append("| field | value |")
        out.append("|---|---|")
        out.append("| good / total | %d / %d |" % (good, total))
        out.append("| attainment | %.4f |" % (good / total))
        burns = {k: v for k, v in m.items()
                 if k.startswith("cxxnet_serve_slo_burn_rate")}
        for k, v in sorted(burns.items()):
            out.append("| burn rate %s | %s |"
                       % (k.split("{", 1)[-1].rstrip("}"), _fmt(v)))
        out.append("")


def section_critical_path(cp: Optional[Dict], out: List[str]) -> None:
    """Critical path from tools/trace_assemble.py's --report JSON:
    where train-step / serve-request time went, attributed to the
    owning process — the "why was it slow" answer next to the "what
    happened" timelines. A wrong-shaped interior (hand-edited,
    version-skewed) drops ONLY this section: the run report must
    render without the fleet trace."""
    if not cp:
        return
    sec: List[str] = []
    try:
        _critical_path_lines(cp, sec)
    except (AttributeError, TypeError, ValueError, KeyError):
        return
    out.extend(sec)


def _critical_path_lines(cp: Dict, out: List[str]) -> None:
    out.append("## Critical path")
    out.append("")
    procs = cp.get("processes") or []
    if procs:
        out.append("%d process(es) assembled, %d cross-process flow "
                   "link(s), %d chain violation(s)"
                   % (len(procs), cp.get("flow_links", 0),
                      len(cp.get("violations") or [])))
        out.append("")
    train = cp.get("train")
    if train:
        out.append("**Train** — %d step(s), mean step wall %s ms"
                   % (train.get("steps", 0),
                      _fmt(train.get("step_wall_mean_us", 0) / 1e3)))
        out.append("")
        out.append("| segment | mean ms | share |")
        out.append("|---|---|---|")
        for name, seg in sorted((train.get("segments") or {}).items()):
            out.append("| %s | %s | %s%% |" % (
                name, _fmt(seg.get("mean_us", 0) / 1e3),
                _fmt(seg.get("pct", 0))))
        out.append("")
        owners = train.get("data_wait_owner_us") or {}
        if owners:
            total = sum(owners.values()) or 1.0
            out.append("data wait by owning process: "
                       + ", ".join("%s %s%%" % (k, _fmt(100 * v / total))
                                   for k, v in sorted(
                                       owners.items(),
                                       key=lambda kv: -kv[1])))
            out.append("")
    serve = cp.get("serve")
    if serve:
        e2e = serve.get("e2e_us") or {}
        out.append("**Serve** — %d request(s), e2e p50 %s ms / p99 %s ms"
                   % (serve.get("requests", 0),
                      _fmt(e2e.get("p50", 0) / 1e3),
                      _fmt(e2e.get("p99", 0) / 1e3)))
        out.append("")
        out.append("| segment | mean ms | p99 ms | share |")
        out.append("|---|---|---|---|")
        for name, seg in sorted((serve.get("segments") or {}).items()):
            out.append("| %s | %s | %s | %s%% |" % (
                name, _fmt(seg.get("mean_us", 0) / 1e3),
                _fmt(seg.get("p99_us", 0) / 1e3),
                _fmt(seg.get("pct", 0))))
        out.append("")
        slow = serve.get("slowest_requests") or []
        if slow:
            t = slow[0]
            out.append("slowest request: %s ms end-to-end (trace `%s`)"
                       % (_fmt(t.get("e2e_us", 0) / 1e3),
                          t.get("trace_id", "?")))
            out.append("")


def section_bench(paths: List[str], out: List[str]) -> None:
    """BENCH_r*.json trajectory. Two shapes are accepted: the driver's
    wrapper (``{"n", "rc", "parsed": {...}|null}`` — a
    ``parsed: null`` renders as a failed round, which is itself signal)
    and a bare bench emit."""
    entries = []
    for p in paths:
        try:
            with open(p, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if "parsed" in doc or "rc" in doc:         # driver wrapper
            entries.append((os.path.basename(p), doc.get("rc"),
                            doc.get("parsed")))
        else:
            entries.append((os.path.basename(p), 0, doc))
    if not entries:
        return
    out.append("## Bench trajectory")
    out.append("")
    out.append("| artifact | value | unit | mfu % | roofline % | note |")
    out.append("|---|---|---|---|---|---|")
    for name, rc, parsed in sorted(entries):
        if not parsed:
            out.append("| %s | — | | | | rc=%s, parsed=null |"
                       % (name, rc))
            continue
        out.append("| %s | %s | %s | %s | %s | %s |" % (
            name, _fmt(parsed.get("value", "")), parsed.get("unit", ""),
            _fmt(parsed.get("mfu_pct", "")),
            _fmt(parsed.get("roofline_pct", "")),
            "truncated" if parsed.get("truncated_phases") else ""))
    out.append("")


def load_trace_report(path: str) -> Optional[Dict[str, Any]]:
    """trace_assemble.py --report JSON; None (section skipped) on any
    malformation — the run report must render without the fleet trace."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return doc if isinstance(doc, dict) else None


def generate(ledger_path: str, telemetry_log: Optional[str],
             bench_paths: List[str],
             trace_report: Optional[str] = None) -> str:
    events = load_ledger(ledger_path) if ledger_path else []
    snap = load_last_snapshot(telemetry_log) if telemetry_log else None
    cp = load_trace_report(trace_report) if trace_report else None
    out: List[str] = []
    section_identity(events, out)
    section_rounds(events, out)
    section_incidents(events, out, ledger_path=ledger_path or "")
    section_modelhealth(events, out)
    section_serving(events, out)
    section_deployments(events, out)
    section_quantization(events, out)
    section_topology(events, out)
    section_checkpoints(events, out)
    section_critical_path(cp, out)
    section_telemetry(snap, out)
    section_bench(bench_paths, out)
    out.append("---")
    out.append("*generated by tools/report.py from `%s`*"
               % (ledger_path or "<no ledger>"))
    return "\n".join(out) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--ledger", required=True,
                    help="run-ledger JSONL (telemetry_ledger=...)")
    ap.add_argument("--telemetry-log", default="",
                    help="telemetry_log JSONL (last snapshot is used)")
    ap.add_argument("--bench", nargs="*", default=[],
                    help="BENCH_r*.json paths or globs")
    ap.add_argument("--trace-report", default="",
                    help="critical-path JSON from tools/"
                         "trace_assemble.py --report")
    ap.add_argument("-o", "--out", default="",
                    help="output path (default: stdout)")
    args = ap.parse_args(argv)
    bench: List[str] = []
    for pat in args.bench:
        hits = sorted(glob.glob(pat))
        bench.extend(hits if hits else [pat])
    md = generate(args.ledger, args.telemetry_log or None, bench,
                  trace_report=args.trace_report or None)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(md)
        print("report -> %s" % args.out)
    else:
        sys.stdout.write(md)
    return 0


if __name__ == "__main__":
    sys.exit(main())
