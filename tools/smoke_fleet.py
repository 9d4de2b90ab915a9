#!/usr/bin/env python
"""Fleet observability smoke (tier-1-adjacent; CPU-safe, two processes).

Drives the PR-7 fleet layer end to end — the acceptance run:

  1. Launch TWO independent train processes (no jax.distributed needed;
     ``telemetry_host`` assigns fleet identity) sharing one run_id, one
     ledger file (O_APPEND interleaving), and one snapshot fleet dir.
     Host 1 trains a deliberately heavier model -> a REAL straggler.
     Host 0 also performs a hang-watchdog DRY RUN (full capture ->
     ledger path, no hang counted).
  2. Merge the pushed snapshots and assert the fleet semantics:
     counters SUM across hosts, per-host histograms survive with their
     counts, and the merged ``/metrics`` — scraped over HTTP — carries
     ``host="0"`` / ``host="1"`` / ``host="fleet"`` labels.
  3. Run the straggler rule on the merged view and assert host 1 is
     flagged (and host 0 is not).
  4. Assert the ledger carries both hosts' run_start/round_end/
     ckpt_save/run_end plus the dry-run hang_dump WITH stacks.
  5. Render a run report (tools/report.py) from the ledger + host 0's
     telemetry_log + the bench-artifact fixtures under tests/data/ and
     assert its sections landed.

Exits nonzero on any failure.  Run:  JAX_PLATFORMS=cpu python tools/smoke_fleet.py
(sibling of tools/smoke_telemetry.py / smoke_serve.py / chaos_train.py)
"""

import glob
import json
import os
import subprocess
import sys
import tempfile
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

NET_TMPL = """
netconfig=start
layer[+1:h1] = fullc:fc1
  nhidden = %(nhidden)d
  random_type = xavier
layer[+1:a1] = relu
layer[a1->out] = fullc:fc2
  nhidden = 5
  random_type = xavier
layer[+0] = softmax
netconfig=end
input_shape = 1,1,%(width)d
batch_size = %(batch)d
eta = 0.1
dev = cpu
eval_train = 0
print_step = 0
silent = 1
save_period = 1
metric = error
num_round = 3
data = train
iter = synthetic
  num_inst = %(num_inst)d
  num_class = 5
  input_shape = 1,1,%(width)d
  seed_data = 3
iter = end
"""


def child_conf(td, host, *, nhidden, width, batch, num_inst, extra=""):
    cfg = NET_TMPL % dict(nhidden=nhidden, width=width, batch=batch,
                          num_inst=num_inst)
    cfg += f"model_dir = {os.path.join(td, 'models%d' % host)}\n"
    cfg += f"telemetry_host = {host}\n"
    cfg += f"telemetry_ledger = {os.path.join(td, 'run.ledger.jsonl')}\n"
    cfg += f"telemetry_fleet_dir = {os.path.join(td, 'fleet')}\n"
    cfg += "telemetry_push_interval = 0.5\n"
    cfg += "telemetry_sync_interval = 2\n"
    cfg += extra
    path = os.path.join(td, f"host{host}.conf")
    with open(path, "w") as f:
        f.write(cfg)
    return path


def main() -> int:
    from cxxnet_tpu.telemetry import MetricsServer
    from cxxnet_tpu.telemetry.aggregate import (merge_snapshots,
                                                read_snapshots,
                                                render_fleet)
    from cxxnet_tpu.telemetry.anomaly import StragglerDetector
    from cxxnet_tpu.telemetry.ledger import read_ledger

    td = tempfile.mkdtemp(prefix="smoke_fleet_")
    run_id = "smoke-fleet-0001"
    tel_log = os.path.join(td, "tel0.jsonl")

    # host 0: small/fast, plus the hang-watchdog dry run + JSONL log
    conf0 = child_conf(
        td, 0, nhidden=16, width=16, batch=64, num_inst=512,
        extra=("telemetry_hang_dryrun = 1\n"
               f"telemetry_log = {tel_log}\n"
               "telemetry_log_interval = 0.5\n"))
    # host 1: ~1000x the matmul work per example and a bigger batch — a
    # genuinely slow host (think: one process landed on busy/old
    # hardware), not a simulated one
    conf1 = child_conf(td, 1, nhidden=2048, width=512, batch=256,
                       num_inst=1024)

    env = dict(os.environ, JAX_PLATFORMS="cpu", CXXNET_RUN_ID=run_id)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "cxxnet_tpu.main", conf],
        cwd=_REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT) for conf in (conf0, conf1)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out.decode("utf-8", "replace"))
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, \
            f"host {i} exited {p.returncode}:\n{out[-4000:]}"

    # ---- merged fleet snapshot ------------------------------------------
    snaps = read_snapshots(os.path.join(td, "fleet"))
    assert {s["host"] for s in snaps} == {0, 1}, \
        f"expected snapshots from both hosts, got {[s['host'] for s in snaps]}"
    view = merge_snapshots(snaps)
    steps = {h: dict(view.host_samples("cxxnet_steptime_steps_total", h)
                     ).get((), 0) for h in (0, 1)}
    assert steps[0] and steps[1], f"both hosts must have stepped: {steps}"
    fleet_steps = view.fleet_counter("cxxnet_steptime_steps_total")[()]
    assert fleet_steps == steps[0] + steps[1], \
        f"fleet counter must SUM: {fleet_steps} != {steps}"
    hists = {h: dict(view.host_samples("cxxnet_steptime_step_seconds", h)
                     ).get(()) for h in (0, 1)}
    assert all(hists[h] and hists[h]["count"] >= 8 for h in (0, 1)), \
        f"per-host step-time histograms too thin: " \
        f"{ {h: hists[h] and hists[h]['count'] for h in (0, 1)} }"

    # ---- merged /metrics over HTTP with host labels ---------------------
    srv = MetricsServer(port=0, render_fn=lambda: render_fleet(view))
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
            body = r.read().decode("utf-8")
    finally:
        srv.stop()
    for needle in ('host="0"', 'host="1"', 'host="fleet"',
                   "cxxnet_steptime_step_seconds_bucket",
                   "cxxnet_run_info"):
        assert needle in body, f"{needle!r} missing from fleet /metrics"

    # ---- straggler verdict ----------------------------------------------
    det = StragglerDetector(factor=2.0, min_steps=8)
    verdicts = det.verdicts(view)
    assert [v["host"] for v in verdicts] == [1], \
        f"expected host 1 (and only host 1) flagged: {verdicts}\n" \
        f"medians: h0={hists[0]['sum']/max(hists[0]['count'],1):.4f}s " \
        f"h1={hists[1]['sum']/max(hists[1]['count'],1):.4f}s"
    assert verdicts[0]["ratio"] > 2.0

    # ---- ledger ---------------------------------------------------------
    ledger_path = os.path.join(td, "run.ledger.jsonl")
    events = read_ledger(ledger_path)
    assert all(e["run_id"] == run_id for e in events)
    by_type = {}
    for e in events:
        by_type.setdefault(e["event"], []).append(e)
    for etype, hosts in (("run_start", {0, 1}), ("round_end", {0, 1}),
                         ("ckpt_save", {0, 1}), ("run_end", {0, 1})):
        got = {e.get("host") for e in by_type.get(etype, [])}
        assert hosts <= got, f"{etype}: hosts {hosts} expected, got {got}"
    dumps = by_type.get("hang_dump", [])
    assert dumps and dumps[0].get("dry_run") and \
        "thread" in dumps[0].get("stacks", "").lower(), \
        f"dry-run hang dump with stacks missing: {dumps and dumps[0]}"
    assert all(e.get("status") == "ok" for e in by_type["run_end"])

    # parent plays the offline aggregator: its straggler finding joins
    # the same ledger the report below reads
    from cxxnet_tpu.telemetry.ledger import LEDGER
    LEDGER.enable(ledger_path, run_id, host=0)
    det.check(view, round_no=None)

    # ---- run report -----------------------------------------------------
    report_path = os.path.join(td, "REPORT.md")
    rc = subprocess.call(
        [sys.executable, os.path.join(_REPO, "tools", "report.py"),
         "--ledger", ledger_path, "--telemetry-log", tel_log,
         "--bench", os.path.join(_REPO, "tests", "data",
                                 "bench_fixture_*.json"),
         "-o", report_path], cwd=_REPO)
    assert rc == 0, "report.py failed"
    md = open(report_path, encoding="utf-8").read()
    for needle in ("# Run report", run_id, "Round trajectory",
                   "hang_dump", "straggler", "## Bench trajectory",
                   "bench_fixture_parsed.json", "parsed=null"):
        assert needle in md, f"{needle!r} missing from report:\n{md[:2000]}"

    print("smoke_fleet OK:", json.dumps({
        "steps": steps, "fleet_steps": fleet_steps,
        "straggler": verdicts[0],
        "ledger_events": {k: len(v) for k, v in sorted(by_type.items())},
        "report_bytes": len(md)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
