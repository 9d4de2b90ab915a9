"""Fleet observability tests (doc/tasks.md "Fleet observability"):

* run ledger — append/read round trip, open-world golden-schema reads
  (unknown event types + fields pass through, malformed lines skipped),
  oversized-payload truncation stays valid JSON, run-info metric;
* mergeable snapshots — property tests that merge is commutative and
  associative, counters sum / gauges stay per-host / histograms merge
  bucket-wise, quantile estimates survive merging, fleet exposition
  carries host labels;
* anomaly detection — straggler rule (median vs fleet median),
  hang-watchdog arm/dump/re-arm on an injected clock, recompile-storm
  windowing;
* serve SLO — good/bad classification, burn-rate arithmetic, window
  expiry, ServingStats wiring, /healthz degradation and /statz run
  identity on a live ServeServer;
* satellites — collect-callback gauges can't go stale (io prefetch
  gauge included), the bench budget watchdog always lands its final
  JSON line, and bench.main refuses a platform that is not a TPU.
"""

import json
import math
import os
import random
import subprocess
import sys
import urllib.request

import pytest

from cxxnet_tpu.telemetry import aggregate, anomaly, ledger, slo
from cxxnet_tpu.telemetry.registry import REGISTRY, MetricRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- ledger -------------------------------------------------------------------

def test_ledger_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    led = ledger.RunLedger(path, run_id="t-1", host=3)
    led.event("run_start", task="train", devices=8)
    led.event("round_end", round=0, images=512)
    evs = ledger.read_ledger(path)
    assert [e["event"] for e in evs] == ["run_start", "round_end"]
    assert all(e["schema"] == ledger.LEDGER_SCHEMA for e in evs)
    assert all(e["run_id"] == "t-1" and e["host"] == 3 for e in evs)
    assert evs[0]["devices"] == 8 and evs[1]["round"] == 0


GOLDEN_LEDGER = "\n".join([
    # a v1 ledger as PR 7 writes it ...
    '{"schema": 1, "ts": 1754000000.0, "run_id": "g", "host": 0, '
    '"event": "run_start", "task": "train", "config_hash": "abc"}',
    '{"schema": 1, "ts": 1754000001.0, "run_id": "g", "host": 0, '
    '"event": "round_end", "round": 0}',
    # ... an event type from the FUTURE with unknown fields ...
    '{"schema": 2, "ts": 1754000002.0, "run_id": "g", "host": 1, '
    '"event": "quantum_flux_trip", "flux": [1, 2], "novel": {"a": 1}}',
    # ... a torn tail write and assorted garbage: all skipped
    '{"schema": 1, "ts": 1754000003.0, "run_id": "g", "ev',
    'not json at all',
    '42',
    "",
])


def test_ledger_golden_schema_open_world(tmp_path):
    """The reader contract: known events parse, unknown event types and
    fields pass through untouched, malformed lines never raise."""
    path = str(tmp_path / "golden.jsonl")
    with open(path, "w") as f:
        f.write(GOLDEN_LEDGER + "\n")
    evs = ledger.read_ledger(path)
    assert [e["event"] for e in evs] == [
        "run_start", "round_end", "quantum_flux_trip"]
    flux = evs[2]
    assert flux["flux"] == [1, 2] and flux["novel"] == {"a": 1}
    assert flux["schema"] == 2          # future schema tolerated on read


def test_ledger_truncates_huge_payload_to_valid_json(tmp_path):
    path = str(tmp_path / "big.jsonl")
    led = ledger.RunLedger(path, run_id="t", host=0)
    led.event("hang_dump", stacks="Thread 0x1\n" + "x" * 100_000,
              note="small survives")
    evs = ledger.read_ledger(path)            # would be [] on torn JSON
    assert len(evs) == 1
    e = evs[0]
    assert e["event"] == "hang_dump"
    assert len(json.dumps(e)) < 4096
    assert e.get("truncated") or e["stacks"].startswith("Thread 0x1")


def test_ledger_proxy_disabled_is_noop_and_enable(tmp_path):
    lp = ledger._LedgerProxy()
    lp.event("whatever", x=1)                 # no file, no error
    assert not lp.enabled
    path = str(tmp_path / "p.jsonl")
    lp.enable(path, "rid", host=2)
    lp.event("run_start")
    assert lp.enabled and lp.events_written == 1
    assert ledger.read_ledger(path)[0]["host"] == 2


def test_ledger_envelope_fields_protected(tmp_path):
    """Payload keys must never clobber the envelope: the envelope's
    host is the WRITER'S provenance, not the event's subject."""
    path = str(tmp_path / "l.jsonl")
    led = ledger.RunLedger(path, run_id="real", host=0)
    led.event("x", host=9, run_id="fake", schema=99, ts=-1, payload=7)
    e = ledger.read_ledger(path)[0]
    assert e["host"] == 0 and e["run_id"] == "real"
    assert e["schema"] == ledger.LEDGER_SCHEMA and e["ts"] > 0
    assert e["payload"] == 7


def test_run_info_metric():
    ledger.set_run_info("rid-123", "cafef00d1234")
    fam = REGISTRY.get("cxxnet_run_info")
    samples = dict(fam.samples())
    assert samples[("rid-123", "cafef00d1234")].value == 1.0
    assert ledger.run_info()["run_id"] == "rid-123"


def test_config_hash_order_sensitive():
    a = ledger.config_hash([("x", "1"), ("y", "2")])
    b = ledger.config_hash([("y", "2"), ("x", "1")])
    assert a != b and len(a) == 12
    assert a == ledger.config_hash([("x", "1"), ("y", "2")])


# -- mergeable snapshots ------------------------------------------------------

def _mk_host_registry(seed, nobs=40):
    """A registry with one counter, one gauge, one histogram populated
    from a seeded RNG, plus the observations that went in."""
    rng = random.Random(seed)
    reg = MetricRegistry()
    reg.counter("work_total").inc(rng.randrange(1, 100))
    reg.gauge("depth").set(rng.randrange(0, 50))
    h = reg.histogram("lat_seconds")               # default buckets
    obs = [10 ** rng.uniform(-4, 0) for _ in range(nobs)]
    for v in obs:
        h.observe(v)
    lab = reg.counter("events_total", labels=("kind",))
    lab.labels("a").inc(seed + 1)
    lab.labels("b").inc(2 * seed + 1)
    return reg, obs


def _canon2(view):
    """Canonical comparable form of a FleetView's DERIVED aggregates."""
    return json.loads(json.dumps({
        "hosts": view.hosts,
        "counters": {n: {str(k): v for k, v
                         in view.fleet_counter(n).items()}
                     for n in view.family_names()},
        "hists": {n: {str(k): v for k, v
                      in view.fleet_histogram(n).items()}
                  for n in view.family_names()},
    }, sort_keys=True))


def test_merge_commutative_associative():
    snaps = [aggregate.export_snapshot(_mk_host_registry(s)[0], host=s)
             for s in range(3)]
    a, b, c = snaps
    ab = aggregate.merge_snapshots([a, b])
    ba = aggregate.merge_snapshots([b, a])
    assert _canon2(ab) == _canon2(ba)
    left = aggregate.merge_snapshots([aggregate.merge_snapshots([a, b]), c])
    right = aggregate.merge_snapshots([a, aggregate.merge_snapshots([b, c])])
    flat = aggregate.merge_snapshots([a, b, c])
    assert _canon2(left) == _canon2(right) == _canon2(flat)


def test_merge_semantics_counters_gauges_histograms():
    regs = [_mk_host_registry(s) for s in (1, 2)]
    view = aggregate.merge_snapshots(
        [aggregate.export_snapshot(r, host=i)
         for i, (r, _) in enumerate(regs)])
    # counters SUM (labeled children sum per label tuple)
    tot = sum(r.counter("work_total").value for r, _ in regs)
    assert view.fleet_counter("work_total")[()] == tot
    for kind in ("a", "b"):
        exp = sum(r.counter("events_total", labels=("kind",))
                  .labels(kind).value for r, _ in regs)
        assert view.fleet_counter("events_total")[(kind,)] == exp
    # gauges keep per-host: no fleet aggregate, per-host values intact
    for h, (r, _) in enumerate(regs):
        assert dict(view.host_samples("depth", h))[()] \
            == r.gauge("depth").value
    # histograms merge bucket-wise: fleet count == sum of host counts
    fh = view.fleet_histogram("lat_seconds")[()]
    assert fh["count"] == sum(len(obs) for _, obs in regs)
    assert fh["sum"] == pytest.approx(
        sum(sum(obs) for _, obs in regs))
    assert sum(fh["counts"]) == fh["count"]


def test_quantile_survives_merge():
    """The merged histogram's quantile must agree with the quantile of
    the POOLED observations to within one bucket's relative width
    (buckets are 3/decade => edges ~2.15x apart)."""
    regs = [_mk_host_registry(s, nobs=400) for s in (5, 6, 7)]
    view = aggregate.merge_snapshots(
        [aggregate.export_snapshot(r, host=i)
         for i, (r, _) in enumerate(regs)])
    pooled = sorted(sum((obs for _, obs in regs), []))
    fh = view.fleet_histogram("lat_seconds")[()]
    for q in (0.1, 0.5, 0.9):
        est = aggregate.quantile(fh["buckets"], fh["counts"], q)
        true = pooled[int(q * (len(pooled) - 1))]
        assert true / 2.16 <= est <= true * 2.16, \
            f"q={q}: est {est} vs true {true}"


def test_quantile_edge_cases():
    assert math.isnan(aggregate.quantile([1.0], [0, 0], 0.5))
    # all mass in the overflow bucket clamps to the last finite edge
    assert aggregate.quantile([1.0, 2.0], [0, 0, 10], 0.5) == 2.0
    # interpolation inside one bucket
    est = aggregate.quantile([1.0, 2.0], [0, 10, 0], 0.5)
    assert 1.0 < est < 2.0


def test_hist_merge_mismatched_buckets_stays_per_host():
    r1, r2 = MetricRegistry(), MetricRegistry()
    r1.histogram("h_seconds", buckets=(1.0, 2.0)).observe(1.5)
    r2.histogram("h_seconds", buckets=(1.0, 4.0)).observe(3.0)
    view = aggregate.merge_snapshots([
        aggregate.export_snapshot(r1, host=0),
        aggregate.export_snapshot(r2, host=1)])
    fh = view.fleet_histogram("h_seconds")[()]
    assert fh["count"] == 1          # only the edge-compatible host(s)
    txt = aggregate.render_fleet(view)
    assert 'host="0"' in txt and 'host="1"' in txt   # both still render


def test_render_fleet_host_labels():
    regs = [_mk_host_registry(s)[0] for s in (1, 2)]
    view = aggregate.merge_snapshots(
        [aggregate.export_snapshot(r, host=i) for i, r in enumerate(regs)])
    txt = aggregate.render_fleet(view)
    assert 'work_total{host="0"}' in txt
    assert 'work_total{host="fleet"}' in txt
    assert 'depth{host="0"}' in txt and 'depth{host="1"}' in txt
    assert 'depth{host="fleet"}' not in txt          # gauges: no sum
    assert 'lat_seconds_bucket{host="fleet",le=' in txt
    # exposition parses: every non-comment line is "name{...} value"
    for line in txt.strip().splitlines():
        if line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        float(val)
        assert key


def test_render_fleet_no_duplicate_host_label():
    """Families that already carry a 'host' label (the straggler
    series live in the aggregating host's registry) must not get the
    writer-host label prepended — duplicate label names are invalid
    exposition and kill the whole scrape."""
    reg = MetricRegistry()
    reg.counter("cxxnet_stragglers_total", "x",
                labels=("host",)).labels("1").inc()
    reg.gauge("cxxnet_straggler_ratio", "x",
              labels=("host",)).labels("1").set(3.2)
    reg.counter("work_total").inc(5)
    view = aggregate.merge_snapshots([
        aggregate.export_snapshot(reg, host=0)])
    txt = aggregate.render_fleet(view)
    assert 'cxxnet_straggler_ratio{host="1"} 3.2' in txt
    assert 'cxxnet_stragglers_total{host="1"} 1' in txt
    assert 'work_total{host="0"} 5' in txt
    for line in txt.strip().splitlines():
        if line.startswith("#") or "{" not in line:
            continue
        labels = line[line.index("{") + 1:line.index("}")]
        names = [p.split("=")[0] for p in labels.split(",") if p]
        assert len(names) == len(set(names)), \
            f"duplicate label name in: {line}"


def test_ledger_nan_inf_sanitized(tmp_path):
    """A diverged run's NaN loss must not produce a bare NaN token —
    the ledger's lines must stay strict JSON for jq/JSON.parse."""
    path = str(tmp_path / "l.jsonl")
    led = ledger.RunLedger(path, run_id="t", host=0)
    led.event("round_end", round=0, loss=float("nan"),
              nested={"a": [1.0, float("inf")]}, fine=1.5)
    raw = open(path).read()
    assert "NaN" not in raw and "Infinity" not in raw
    e = json.loads(raw, parse_constant=lambda c: (_ for _ in ()).throw(
        ValueError(c)))
    assert e["loss"] is None and e["nested"]["a"] == [1.0, None]
    assert e["fine"] == 1.5


def test_push_read_snapshots_atomic(tmp_path):
    reg, _ = _mk_host_registry(4)
    d = str(tmp_path / "fleet")
    aggregate.write_snapshot(d, host=2, registry=reg)
    # a torn/garbage file in the dir is skipped, not fatal
    with open(os.path.join(d, "host_9.json"), "w") as f:
        f.write('{"schema": 1, "host":')
    with open(os.path.join(d, "not_a_snapshot.json"), "w") as f:
        f.write("{}")
    snaps = aggregate.read_snapshots(d)
    assert [s["host"] for s in snaps] == [2]
    assert aggregate.read_snapshots(d, skip_host=2) == []


def test_read_snapshots_run_id_filter(tmp_path):
    """A persistent shared fleet dir accumulates files from previous
    runs; an aggregator keyed to its run_id must not merge them."""
    d = str(tmp_path / "fleet")
    reg = _mk_host_registry(1)[0]
    aggregate.write_snapshot(d, host=0, registry=reg, run_id="run-A")
    aggregate.write_snapshot(d, host=1, registry=reg, run_id="run-B")
    aggregate.write_snapshot(d, host=2, registry=reg)      # unstamped
    assert [s["host"] for s in aggregate.read_snapshots(d)] == [0, 1, 2]
    assert [s["host"] for s in
            aggregate.read_snapshots(d, run_id="run-A")] == [0]
    assert [s["host"] for s in
            aggregate.read_snapshots(d, run_id="run-C")] == []


def test_snapshot_evaluates_callback_gauges():
    """Collect-callback gauges resolve at snapshot time — a pushed
    snapshot can never carry a stale queue depth."""
    reg = MetricRegistry()
    box = {"v": 1.0}
    reg.gauge("live_depth").set_function(lambda: box["v"])
    assert aggregate.export_snapshot(reg)["families"][
        "live_depth"]["samples"][0][1] == 1.0
    box["v"] = 42.0
    assert aggregate.export_snapshot(reg)["families"][
        "live_depth"]["samples"][0][1] == 42.0
    assert reg.snapshot()["live_depth"] == 42.0


def test_io_prefetch_gauge_is_callback_backed():
    """Satellite: the threadbuffer depth gauge reads the live queue."""
    from cxxnet_tpu.io.data import create_iterator
    from cxxnet_tpu.config import parse_config_string
    fam = REGISTRY.get("cxxnet_io_prefetch_queue_depth")
    before = {vals for vals, _ in fam.samples()} if fam else set()
    it = create_iterator(parse_config_string("""
iter = synthetic
num_inst = 64
batch_size = 16
num_class = 5
input_shape = 1,1,8
iter = threadbuffer
buffer_size = 2
iter = end
"""))
    batches = list(it)
    assert len(batches) == 4
    fam = REGISTRY.get("cxxnet_io_prefetch_queue_depth")
    mine = [c for vals, c in fam.samples() if vals not in before]
    assert mine, "iterator registered no depth gauge"
    child = mine[0]
    assert child._fn is not None, "depth gauge must be callback-backed"
    assert child.value == 0.0          # fully drained queue reads live


# -- anomaly: stragglers ------------------------------------------------------

def _steptime_view(per_host_ms):
    """FleetView whose cxxnet_steptime_step_seconds per host is built
    from the given per-step millisecond samples."""
    snaps = []
    for h, samples in per_host_ms.items():
        reg = MetricRegistry()
        hist = reg.histogram(anomaly.STEP_SECONDS_METRIC)
        for ms in samples:
            hist.observe(ms / 1e3)
        snaps.append(aggregate.export_snapshot(reg, host=h))
    return aggregate.merge_snapshots(snaps)


def test_straggler_detected():
    view = _steptime_view({0: [10] * 50, 1: [11] * 50, 2: [80] * 50})
    det = anomaly.StragglerDetector(factor=2.0, min_steps=8,
                                    registry=MetricRegistry())
    v = det.verdicts(view)
    assert [x["host"] for x in v] == [2]
    assert v[0]["ratio"] > 2.0


def test_straggler_not_flagged_within_factor():
    view = _steptime_view({0: [10] * 50, 1: [15] * 50})
    det = anomaly.StragglerDetector(factor=2.0, min_steps=8,
                                    registry=MetricRegistry())
    assert det.verdicts(view) == []


def test_straggler_needs_min_steps_and_two_hosts():
    det = anomaly.StragglerDetector(factor=2.0, min_steps=8,
                                    registry=MetricRegistry())
    assert det.verdicts(_steptime_view({0: [10] * 50})) == []
    assert det.verdicts(
        _steptime_view({0: [10] * 4, 1: [99] * 4})) == []


def test_straggler_onset_windowed_and_ledgered_once(tmp_path):
    """check() compares per-check DELTAS (growing cumulative
    histograms, like a live run): one onset event per stretch of
    slowness, recovery re-arms."""
    lp = ledger.LEDGER
    lp.enable(str(tmp_path / "l.jsonl"), "r", host=0)
    try:
        det = anomaly.StragglerDetector(factor=2.0, min_steps=8,
                                        registry=MetricRegistry())
        obs = {0: [10] * 50, 1: [80] * 50}
        assert len(det.check(_steptime_view(obs), 1)) == 1
        obs = {0: obs[0] + [10] * 50, 1: obs[1] + [80] * 50}
        assert len(det.check(_steptime_view(obs), 2)) == 1  # still slow
        evs = [e for e in ledger.read_ledger(str(tmp_path / "l.jsonl"))
               if e["event"] == "straggler"]
        # one event per onset; envelope host = the WRITER (this
        # aggregator), payload straggler_host = the flagged host
        assert len(evs) == 1 and evs[0]["straggler_host"] == 1
        assert evs[0]["host"] == 0
        # recovery: host 1's RECENT window is healthy — re-arms
        obs = {0: obs[0] + [10] * 50, 1: obs[1] + [10] * 50}
        assert det.check(_steptime_view(obs), 3) == []
        obs = {0: obs[0] + [10] * 50, 1: obs[1] + [80] * 50}
        assert len(det.check(_steptime_view(obs), 4)) == 1
        evs = [e for e in ledger.read_ledger(str(tmp_path / "l.jsonl"))
               if e["event"] == "straggler"]
        assert len(evs) == 2
    finally:
        lp.disable()


def test_straggler_late_onset_detected():
    """A host that degrades AFTER a long healthy history must be
    flagged from its recent window — its lifetime median never
    moves (the cumulative-histogram trap)."""
    det = anomaly.StragglerDetector(factor=2.0, min_steps=8,
                                    registry=MetricRegistry())
    obs = {0: [10] * 500, 1: [10] * 500}
    assert det.check(_steptime_view(obs), 1) == []
    obs = {0: obs[0] + [10] * 20, 1: obs[1] + [80] * 20}
    v = det.check(_steptime_view(obs), 2)
    assert [x["host"] for x in v] == [1]
    # whole-history rule on the same data stays blind to it — the
    # reason check() windows
    assert det.verdicts(_steptime_view(obs)) == []


# -- anomaly: hang watchdog ---------------------------------------------------

def test_hang_watchdog_arms_dumps_rearms(tmp_path):
    lp = ledger.LEDGER
    lp.enable(str(tmp_path / "l.jsonl"), "r", host=0)
    try:
        reg = MetricRegistry()
        box = {"steps": 0.0}
        wd = anomaly.HangWatchdog(hang_s=10.0, poll_s=1.0,
                                  progress_fn=lambda: box["steps"],
                                  registry=reg)
        t = 1000.0
        wd._tick(t)                  # baseline: NOT armed
        wd._tick(t + 60)             # long startup compile: no dump
        assert wd.dumps == 0
        box["steps"] = 1.0
        wd._tick(t + 61)             # first progress: armed
        wd._tick(t + 65)             # under hang_s: quiet
        assert wd.dumps == 0
        wd._tick(t + 72)             # stalled 11 s: dump
        assert wd.dumps == 1
        wd._tick(t + 80)             # same stall: no second dump
        assert wd.dumps == 1
        box["steps"] = 2.0
        wd._tick(t + 81)             # progress: re-armed
        wd._tick(t + 95)             # stalled again: second dump
        assert wd.dumps == 2
        assert reg.counter("cxxnet_hangs_total").value == 2
        evs = [e for e in ledger.read_ledger(str(tmp_path / "l.jsonl"))
               if e["event"] == "hang_dump"]
        assert len(evs) == 2
        assert "thread" in evs[0]["stacks"].lower()
        assert evs[0]["stalled_for_s"] >= 10
    finally:
        lp.disable()


def test_hang_watchdog_dry_run_counts_nothing(tmp_path):
    lp = ledger.LEDGER
    lp.enable(str(tmp_path / "l.jsonl"), "r", host=0)
    try:
        reg = MetricRegistry()
        wd = anomaly.HangWatchdog(hang_s=1.0, progress_fn=lambda: 0,
                                  registry=reg)
        stacks = wd.dump_now(dry_run=True)
        assert "thread" in stacks.lower()
        assert wd.dumps == 0
        assert reg.counter("cxxnet_hangs_total").value == 0
        evs = ledger.read_ledger(str(tmp_path / "l.jsonl"))
        assert evs and evs[0]["dry_run"] is True
    finally:
        lp.disable()


# -- anomaly: recompile storms ------------------------------------------------

def test_recompile_storm_grace_then_fire():
    det = anomaly.RecompileStormDetector(window_s=60, threshold=5,
                                         grace=8,
                                         registry=MetricRegistry())
    t = 100.0
    # warmup: 8 compiles quickly — inside grace, no storm
    assert det.observe(8, now=t) is False
    # a real storm: +10 compiles in 30 s
    assert det.observe(18, now=t + 30) is True
    assert det.storms == 1
    # still storming: no NEW onset
    assert det.observe(28, now=t + 50) is True
    assert det.storms == 1
    # rate subsides (old obs roll out of the window): re-arms
    assert det.observe(29, now=t + 200) is False
    assert det.observe(45, now=t + 210) is True
    assert det.storms == 2


def test_recompile_storm_sparse_observations_never_false_fire():
    """One observation per long round (sparser than the window): a
    below-rate drip of compiles must not register as a storm."""
    det = anomaly.RecompileStormDetector(window_s=60, threshold=8,
                                         grace=0,
                                         registry=MetricRegistry())
    t, total = 0.0, 0
    for i in range(6):
        total += 8              # 8 compiles per 600 s = 10x under rate
        assert det.observe(total, now=t + 600.0 * (i + 1)) is False
    assert det.storms == 0


def test_recompile_storm_slow_drip_never_fires():
    det = anomaly.RecompileStormDetector(window_s=60, threshold=5,
                                         grace=0,
                                         registry=MetricRegistry())
    t, total = 100.0, 0
    for i in range(30):
        total += 1
        assert det.observe(total, now=t + 30 * i) is False
    assert det.storms == 0


def test_compile_counter_installs_and_counts():
    assert anomaly.install_compile_counter() is True
    assert anomaly.install_compile_counter() is True      # idempotent
    import jax
    import jax.numpy as jnp
    c = REGISTRY.counter("cxxnet_compiles_total")
    before = c.value
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,)))
    assert c.value > before


# -- serve SLO ----------------------------------------------------------------

class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_slo_classification_and_burn():
    clk = FakeClock()
    t = slo.SLOTracker(slo_ms=100, target=0.9, window_s=60,
                       instance="t0", registry=MetricRegistry(),
                       clock=clk)
    for _ in range(8):
        t.record(latency_s=0.05, ok=True)      # good
    t.record(latency_s=0.5, ok=True)           # over SLO: bad
    t.record(ok=False)                         # reject: bad
    snap = t.snapshot()
    assert snap["good"] == 8 and snap["bad"] == 2
    # burn = (2/10) / (1-0.9) = 2.0
    assert t.burn_rate() == pytest.approx(2.0)
    assert t.attainment() == pytest.approx(0.8)


def test_slo_window_expiry_and_idle():
    clk = FakeClock()
    t = slo.SLOTracker(slo_ms=100, target=0.99, window_s=10,
                       instance="t1", registry=MetricRegistry(),
                       clock=clk)
    assert t.burn_rate() == 0.0                # idle: not burning
    t.record(ok=False)
    assert t.burn_rate() == pytest.approx(100.0)
    clk.t += 100                               # bad events age out
    assert t.burn_rate() == 0.0
    assert t.attainment() == 0.0               # lifetime remembers


def test_slo_validation():
    with pytest.raises(ValueError):
        slo.SLOTracker(slo_ms=0, registry=MetricRegistry())
    with pytest.raises(ValueError):
        slo.SLOTracker(slo_ms=10, target=1.5, registry=MetricRegistry())


def test_serving_stats_feeds_slo():
    from cxxnet_tpu.serve import ServingStats
    stats = ServingStats()
    clk = FakeClock()
    stats.slo = slo.SLOTracker(slo_ms=100, target=0.9, window_s=60,
                               instance=stats.instance, clock=clk)
    stats.record_done(0.01)                    # good
    stats.record_done(0.5)                     # over: bad
    stats.record_reject("backpressure")        # bad
    stats.record_failure()                     # bad
    snap = stats.slo.snapshot()
    assert snap["good"] == 1 and snap["bad"] == 3
    stats.unregister()                         # drops SLO series too
    fam = REGISTRY.get("cxxnet_serve_slo_burn_rate")
    assert all(vals != (stats.instance,) for vals, _ in fam.samples())


def _make_engine(mesh):
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.serve import InferenceEngine
    from cxxnet_tpu.trainer import Trainer
    tr = Trainer(parse_config_string("""
netconfig=start
layer[+1:h1] = fullc:fc1
  nhidden = 24
  random_type = xavier
layer[+1:a1] = relu
layer[a1->out] = fullc:fc2
  nhidden = 5
  random_type = xavier
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = 32
eta = 0.3
metric = error
"""), mesh_ctx=mesh)
    tr.init_model()
    return InferenceEngine(tr, buckets="2,4,8,16", max_batch=16)


def test_serve_server_slo_healthz_statz(mesh1):
    """Live server: burn over the degraded threshold flips /healthz to
    degraded (while the breaker stays closed), /statz carries the slo
    section + run identity."""
    from cxxnet_tpu.serve.server import ServeServer
    ledger.set_run_info("slo-run-1", "beefcafe0000")
    srv = ServeServer(_make_engine(mesh1), port=0, max_latency_ms=2,
                      log_interval_s=0, silent=True,
                      slo_ms=0.0001,           # everything misses
                      slo_target=0.99, slo_window_s=60,
                      slo_burn_degraded=2.0).start()
    try:
        for _ in range(4):
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/predict",
                data=json.dumps({"data": [[0.0] * 16]}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                json.loads(r.read())
        code, health = srv.health()
        assert health["status"] == "degraded" and code == 200
        assert health["breaker"] == "closed"
        assert health["slo_burn_rate"] > 2.0
        stz = srv.statz()
        assert stz["slo"]["bad"] == 4 and stz["slo"]["good"] == 0
        assert stz["run"]["run_id"] == "slo-run-1"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
            body = r.read().decode()
        assert 'cxxnet_serve_slo_requests_total{engine="%s",result="bad"} 4' \
            % srv.stats.instance in body
        assert 'cxxnet_run_info{run_id="slo-run-1"' in body
    finally:
        srv.stop()


def test_serve_server_slo_ok_when_fast(mesh1):
    from cxxnet_tpu.serve.server import ServeServer
    srv = ServeServer(_make_engine(mesh1), port=0, max_latency_ms=2,
                      log_interval_s=0, silent=True,
                      slo_ms=60000).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/predict",
            data=json.dumps({"data": [[0.0] * 16]}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            json.loads(r.read())
        code, health = srv.health()
        assert health["status"] == "ok"
        assert srv.statz()["slo"]["good"] == 1
    finally:
        srv.stop()


# -- steptime histogram -------------------------------------------------------

def test_steptime_probe_populates_step_histogram():
    from cxxnet_tpu.telemetry.steptime import StepTimeProbe
    reg = MetricRegistry()
    probe = StepTimeProbe(sync_interval=2, registry=reg)
    for _ in range(6):
        probe.note_data_wait(0.001)
        probe.record_step(0.002)
    h = reg.histogram("cxxnet_steptime_step_seconds")
    assert h.labels().count == 6            # one observation PER STEP


# -- exporter render_fn -------------------------------------------------------

def test_metrics_server_render_fn_and_fallback():
    from cxxnet_tpu.telemetry.exporter import MetricsServer
    srv = MetricsServer(port=0, render_fn=lambda: "custom_metric 7\n")
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as r:
            assert r.read().decode() == "custom_metric 7\n"

        def boom():
            raise RuntimeError("fleet refresh died")
        srv.render_fn = boom
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as r:
            body = r.read().decode()
        assert "cxxnet" in body            # local-registry fallback
    finally:
        srv.stop()


# -- telemetry config ---------------------------------------------------------

def test_fleet_telemetry_config_knobs():
    from cxxnet_tpu.config import (ConfigError, parse_config_string,
                                   parse_telemetry_config)
    tc = parse_telemetry_config(parse_config_string("""
telemetry_ledger = /tmp/x.jsonl
telemetry_fleet_dir = /tmp/fleet
telemetry_push_interval = 2.5
telemetry_host = 3
telemetry_hang_s = 30
telemetry_straggler_factor = 3.0
"""))
    assert tc.ledger_path == "/tmp/x.jsonl"
    assert tc.fleet_dir == "/tmp/fleet"
    assert tc.push_interval_s == 2.5
    assert tc.host == 3 and tc.hang_s == 30.0
    assert tc.straggler_factor == 3.0
    for bad in ("telemetry_push_interval = 0",
                "telemetry_hang_s = -1",
                "telemetry_straggler_factor = 1.0",
                "telemetry_storm_threshold = 0",
                "telemetry_ledgerr = /x"):
        with pytest.raises(ConfigError):
            parse_telemetry_config(parse_config_string(bad))


# -- report generator ---------------------------------------------------------

def test_report_generates_from_ledger(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import report
    path = str(tmp_path / "l.jsonl")
    led = ledger.RunLedger(path, run_id="rep-1", host=0)
    led.event("run_start", task="train", config_hash="abc",
              process_count=2, devices=8, platform="cpu",
              mesh={"data": 8, "seq": 1, "pipe": 1, "model": 1})
    for r in range(3):
        led.event("round_end", round=r, images=512, seconds=1.5,
                  images_per_sec=341.3, loss=0.5 - 0.1 * r)
    led.event("sentinel_trip", round=2, reason="loss spike 9 > 5x median")
    led.event("rollback", round=2, to_round=1, path="0001.model",
              lr_scale=0.5)
    led.event("breaker_transition", from_state="closed", to_state="open")
    led.event("future_event_type", mystery=1)       # open world
    led.event("run_end", status="ok")
    md = report.generate(path, None,
                         [os.path.join(REPO, "tests", "data",
                                       "bench_fixture_parsed.json"),
                          os.path.join(REPO, "tests", "data",
                                       "bench_fixture_null.json")])
    assert "# Run report — `rep-1`" in md
    assert "status: **ok**" in md
    assert "Round trajectory" in md and "| 2 |" in md
    assert "sentinel_trip" in md and "loss spike" in md
    assert "rollback" in md and "round 2 -> 1" in md
    assert "closed -> open" in md
    assert "future_event_type" in md                 # unknown: listed
    assert "bench_fixture_parsed.json | 1234" in md
    assert "bench_fixture_null.json | — | | | | rc=124, parsed=null" in md


def test_report_critical_path_section_and_malformed_interior(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import report
    path = str(tmp_path / "l.jsonl")
    ledger.RunLedger(path, "cp-1").event("run_start", task="train")
    good = str(tmp_path / "cp.json")
    with open(good, "w") as f:
        json.dump({"processes": [{"pid": 1, "role": "train"}],
                   "flow_links": 2, "violations": [],
                   "train": {"steps": 3, "step_wall_mean_us": 1000.0,
                             "segments": {"h2d": {"mean_us": 10.0,
                                                  "pct": 1.0}},
                             "data_wait_owner_us": {"local": 5.0}}}, f)
    md = report.generate(path, None, [], trace_report=good)
    assert "## Critical path" in md and "h2d" in md
    # a wrong-shaped interior (hand-edited, version-skewed) must drop
    # ONLY this section — the run report renders without the trace
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"processes": [{"pid": 1}], "train": ["x"]}, f)
    md = report.generate(path, None, [], trace_report=bad)
    assert "## Critical path" not in md
    assert "# Run report" in md


def test_report_cli(tmp_path):
    path = str(tmp_path / "l.jsonl")
    ledger.RunLedger(path, "cli-1").event("run_start", task="train")
    out = str(tmp_path / "R.md")
    rc = subprocess.call(
        [sys.executable, os.path.join(REPO, "tools", "report.py"),
         "--ledger", path, "-o", out], cwd=REPO)
    assert rc == 0
    assert "# Run report" in open(out).read()


# -- bench budget watchdog regression (ROADMAP 5a) ----------------------------

def test_bench_budget_watchdog_lands_final_json():
    """A bench run killed by the harness timeout leaves no JSON line.
    Contract under test: when the budget runs out mid-phase the
    watchdog ALWAYS exits 0 with a parseable final JSON line carrying
    what was recorded so far, well before an external kill. (Driven
    through ``bench.Budget`` itself: ``bench.main`` measures the chip
    and refuses this suite's CPU backend — next test.)"""
    code = (
        "import sys, time; sys.path.insert(0, %r); import bench\n"
        "partial = {'metric': "
        "'inception_bn_train_images_per_sec_per_chip', 'value': None}\n"
        "b = bench.Budget(3.0, partial)\n"
        "b.record({'value': 1.0})\n"
        "assert b.low(60, 'some_phase')\n"
        "time.sleep(60)   # a phase that outlives the budget\n" % REPO)
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout at all; stderr: {p.stderr[-1000:]}"
    parsed = json.loads(lines[-1])
    assert parsed["metric"] == "inception_bn_train_images_per_sec_per_chip"
    assert parsed["value"] == 1.0
    assert "some_phase" in parsed["truncated_phases"]


def test_bench_main_refuses_a_non_tpu_platform():
    """bench.py measures the chip: on any other platform it stops with
    a message naming the platform it found — no CPU pin, no child
    process, and no JSON line that could be read as a device number."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--budget-s", "6"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "not a TPU" in p.stderr
    assert p.stdout.strip() == ""
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "subprocess" not in src and "jax_platforms" not in src
