"""cxxnet_tpu.telemetry: registry, tracing, step-time probe, exporter,
profiler — plus the ServingStats//statz key-compat contract and the
ThreadBufferIterator shutdown-hang regression (PR 4 satellites)."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from cxxnet_tpu.telemetry import (REGISTRY, MetricsServer, StepProfiler,
                                  StepTimeProbe, TelemetryLogger, Tracer,
                                  render_prometheus)
from cxxnet_tpu.telemetry.registry import (MetricError, MetricRegistry,
                                           log_buckets)


# -- registry ---------------------------------------------------------------

def test_counter_concurrent_increments_lose_nothing():
    reg = MetricRegistry()
    c = reg.counter("t_conc_total", "concurrency").labels()
    n_threads, n_inc = 8, 2000

    def storm():
        for _ in range(n_inc):
            c.inc()
    ts = [threading.Thread(target=storm) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * n_inc


def test_histogram_bucket_edges():
    reg = MetricRegistry()
    h = reg.histogram("t_h", "edges", buckets=(1.0, 2.0, 4.0)).labels()
    # le-semantics: an observation AT an edge belongs to that edge's
    # bucket; above the top edge -> +Inf only
    for v in (0.5, 1.0, 1.0001, 2.0, 4.0, 4.5):
        h.observe(v)
    cum = dict(h.cumulative())
    assert cum[1.0] == 2          # 0.5, 1.0
    assert cum[2.0] == 4          # + 1.0001, 2.0
    assert cum[4.0] == 5          # + 4.0
    assert cum[float("inf")] == 6
    assert h.count == 6
    assert abs(h.sum - 13.0001) < 1e-9


def test_log_buckets_geometric():
    b = log_buckets(1e-3, 1.0, per_decade=3)
    assert b[0] == 1e-3 and b[-1] >= 1.0
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    for r in ratios:              # 10^(1/3) spacing
        assert abs(r - 10 ** (1 / 3)) < 0.01


def test_registry_get_or_create_and_type_conflicts():
    reg = MetricRegistry()
    a = reg.counter("t_same_total", "x", labels=("k",))
    b = reg.counter("t_same_total", "x", labels=("k",))
    assert a is b                                   # shared family
    a.labels(k="v").inc(3)
    assert b.labels(k="v").value == 3               # shared child
    with pytest.raises(MetricError):
        reg.gauge("t_same_total")                   # kind conflict
    with pytest.raises(MetricError):
        reg.counter("t_same_total", labels=("other",))  # label conflict
    with pytest.raises(MetricError):
        reg.counter("bad name")                     # invalid name


def test_gauge_callback():
    reg = MetricRegistry()
    g = reg.gauge("t_g", "cb")
    g.set_function(lambda: 42.0)
    assert g.value == 42.0
    g.set(7)                                        # set clears the fn
    assert g.value == 7


# -- prometheus exposition --------------------------------------------------

def test_metrics_text_golden():
    reg = MetricRegistry()
    c = reg.counter("app_requests_total", "Requests served",
                    labels=("code",))
    c.labels(code="200").inc(3)
    c.labels(code="500").inc()
    reg.gauge("app_temp", "Temperature").set(36.6)
    h = reg.histogram("app_lat_seconds", "Latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    expected = "\n".join([
        "# HELP app_lat_seconds Latency",
        "# TYPE app_lat_seconds histogram",
        'app_lat_seconds_bucket{le="0.1"} 1',
        'app_lat_seconds_bucket{le="1"} 2',
        'app_lat_seconds_bucket{le="+Inf"} 3',
        "app_lat_seconds_sum 5.55",
        "app_lat_seconds_count 3",
        "# HELP app_requests_total Requests served",
        "# TYPE app_requests_total counter",
        'app_requests_total{code="200"} 3',
        'app_requests_total{code="500"} 1',
        "# HELP app_temp Temperature",
        "# TYPE app_temp gauge",
        "app_temp 36.6",
    ]) + "\n"
    assert render_prometheus(reg) == expected


def _parse_prometheus(text):
    """Minimal exposition-format parser: every non-comment line must be
    ``name{labels} value`` — returns {sample_name_with_labels: float}."""
    out = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        assert key, f"malformed sample line: {line!r}"
        out[key] = float(val)
    return out


def test_metrics_server_scrape(tmp_path):
    reg = MetricRegistry()
    reg.counter("t_scrape_total", "x").inc(5)
    srv = MetricsServer(port=0, registry=reg).start()
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as r:
            body = r.read().decode("utf-8")
            ctype = r.headers.get("Content-Type", "")
    finally:
        srv.stop()
    assert "version=0.0.4" in ctype
    assert _parse_prometheus(body)["t_scrape_total"] == 5.0


# -- tracing ----------------------------------------------------------------

def test_trace_chrome_json_valid_and_nested(tmp_path):
    tr = Tracer(capacity=128)
    tr.enable()
    with tr.span("outer", cat="test", args={"k": "v"}):
        time.sleep(0.002)
        with tr.span("inner", cat="test"):
            time.sleep(0.002)
    path = str(tmp_path / "trace.json")
    n = tr.dump(path)
    assert n == 2
    doc = json.loads(open(path, "rb").read().decode("utf-8"))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_name = {e["name"]: e for e in evs}
    for e in evs:                     # chrome trace-event required keys
        for k in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert k in e, f"event missing {k}: {e}"
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["tid"] == inner["tid"]
    # nesting: inner lies strictly inside outer on the shared timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"k": "v"}


def test_trace_ring_bounds_and_drop_count():
    tr = Tracer(capacity=10)
    tr.enable()
    for i in range(25):
        t0 = time.perf_counter()
        tr.add_complete(f"e{i}", t0, t0)
    evs = tr.events()
    assert len(evs) == 10
    assert tr.dropped == 15
    assert evs[-1]["name"] == "e24"   # newest survive


def test_trace_disabled_is_noop():
    tr = Tracer(capacity=8)
    with tr.span("nope"):
        pass
    tr.add_complete("nope", 0.0, 1.0)
    assert tr.events() == []


def test_trace_kept_category_records_without_enable():
    """keep(): spans of the named categories reach the ring without
    enable() — no sink, no export path — and nothing else does."""
    tr = Tracer(capacity=8)
    tr.keep(("train",))
    with tr.span("train.step_dispatch", cat="train", args={"step": 1}):
        pass
    tr.add_complete("train.data_wait", 1.0, 1.5, cat="train")
    tr.add_complete("serve.infer", 1.0, 1.5, cat="serve")
    with tr.span("ckpt.save", cat="ckpt"):
        pass
    tr.instant("profiler.start_trace", cat="train")
    sunk = []
    tr.set_sink(lambda ev: sunk.append(ev) or True)   # would eat all
    tr.add_complete("train.metric_drain", 2.0, 2.25, cat="train")
    evs = tr.events()
    assert [e["name"] for e in evs] == [
        "train.step_dispatch", "train.data_wait", "train.metric_drain"]
    assert sunk == []
    assert evs[1]["dur"] == pytest.approx(0.5e6)
    assert evs[1]["ts"] == pytest.approx(tr.to_ts_us(1.0))
    assert evs[0]["pid"] == os.getpid() and evs[0]["args"] == {"step": 1}
    for i in range(20):                 # bounded: the newest survive
        tr.add_complete(f"train.e{i}", 3.0, 3.0, cat="train")
    assert len(tr.events()) == 8 and tr.events()[-1]["name"] == "train.e19"
    tr.keep(())
    tr.add_complete("train.data_wait", 1.0, 1.5, cat="train")
    assert tr.events()[-1]["name"] == "train.e19"


_TRAIN_CFG = """
data = train
iter = synthetic
  num_inst = 256
  num_class = 5
  input_shape = 1,1,16
iter = end
netconfig=start
layer[+1:h1] = fullc:fc1
  nhidden = 16
layer[+1:a1] = relu
layer[a1->out] = fullc:fc2
  nhidden = 5
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = 16
eta = 0.1
metric = error
num_round = 2
save_model = 0
dev = cpu
silent = 1
print_step = 0
telemetry_sync_interval = 4
"""

_LOOP_SPANS = {"train.data_wait", "train.h2d_stage", "train.step_dispatch",
               "train.metric_drain", "train.device_block"}


def _train(extra=""):
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.telemetry import TRACER
    TRACER.disable()
    TRACER.clear()
    try:
        LearnTask(parse_config_string(_TRAIN_CFG + extra)).run()
        return TRACER.events()
    finally:
        TRACER.disable()
        TRACER.keep(())
        TRACER.clear()


def test_default_train_run_keeps_the_loops_spans():
    """No telemetry_trace: the ring still holds the loop's own spans,
    and only those beside the set-up's — after the session has closed."""
    evs = _train()
    assert {e["name"] for e in evs} >= _LOOP_SPANS
    assert {e["name"] for e in evs if e.get("cat") == "train"} \
        == _LOOP_SPANS
    assert {e.get("cat") for e in evs} == {"train", "setup"}
    evs = [e for e in evs if e.get("cat") == "train"]
    steps = [e for e in evs if e["name"] == "train.step_dispatch"]
    assert len(steps) == 2 * (256 // 16)
    # the drain is its own span, after the enqueue and outside it
    drains = [e for e in evs if e["name"] == "train.metric_drain"]
    assert len(drains) >= len(steps) - 1
    first = steps[1]
    after = [d for d in drains if d["ts"] >= first["ts"]][0]
    assert after["ts"] >= first["ts"] + first["dur"] - 1e-3
    # h2d_stage nests in data_wait (prefetch_device stages in next())
    waits = [e for e in evs if e["name"] == "train.data_wait"]
    stage = [e for e in evs if e["name"] == "train.h2d_stage"]
    assert any(w["ts"] <= s["ts"] and s["ts"] + s["dur"]
               <= w["ts"] + w["dur"] + 1e-3 for w in waits for s in stage)


def test_steptime_zero_keeps_no_spans():
    assert _train("telemetry_steptime = 0\n") == []


def test_telemetry_trace_still_dumps_every_category(tmp_path):
    path = str(tmp_path / "trace.json")
    _train(f"telemetry_trace = {path}\neval = test\niter = synthetic\n"
           "  num_inst = 32\n  num_class = 5\n  input_shape = 1,1,16\n"
           "iter = end\ntelemetry_profile_steps = 2-3\n"
           f"telemetry_profile_dir = {tmp_path / 'prof'}\n")
    doc = json.loads(open(path, "rb").read().decode("utf-8"))
    names = {e["name"] for e in doc["traceEvents"]}
    assert names >= _LOOP_SPANS | {"train.eval", "profiler.start_trace",
                                   "profiler.stop_trace", "thread_name"}
    cats = {e.get("cat") for e in doc["traceEvents"] if e.get("ph") != "M"}
    assert {"train", "profile"} <= cats


def test_round_log_splits_enqueue_from_drain():
    """record_step(drain_s=..): dispatch_ms is the enqueue, drain_ms the
    wait in the metric drain; the verdict rule reads neither."""
    probe = StepTimeProbe(sync_interval=4, registry=MetricRegistry())
    for _ in range(8):
        probe.note_data_wait(0.0001)
        probe.record_step(dispatch_s=0.002, ready=np.float32(0.0),
                          drain_s=0.050)
    assert probe.dispatch_ema == pytest.approx(0.002)
    assert probe.drain_ema == pytest.approx(0.050)
    frag = probe.report_fragment()
    assert "\tdispatch_ms:2.00\tdrain_ms:50.00\tdevice_ms:" in frag
    assert probe.verdict() == "balanced"    # as without the drain


# -- step-time probe --------------------------------------------------------

class _SyncCountingLoss:
    """Stand-in ready future that counts block_until_ready-style syncs
    (jax.block_until_ready on a non-jax object calls nothing, so the
    probe's sync count is asserted via probe.syncs instead)."""


def test_steptime_probe_classifies_starved_iterator_as_input_bound():
    reg = MetricRegistry()
    probe = StepTimeProbe(sync_interval=4, registry=reg)
    # a starved input pipeline: 20 ms data waits, microsecond dispatch,
    # instantly-ready outputs (None => no device block either)
    for _ in range(12):
        probe.note_data_wait(0.020)
        probe.record_step(dispatch_s=0.0005, ready=np.float32(0.0))
    assert probe.verdict() == "input-bound"
    frag = probe.report_fragment()
    assert "bound:input-bound" in frag and "data_ms:" in frag


def test_steptime_probe_syncs_at_most_once_per_interval():
    probe = StepTimeProbe(sync_interval=5)
    steps = 23
    for _ in range(steps):
        probe.record_step(dispatch_s=0.001, ready=np.float32(0.0))
    assert probe.steps == steps
    # steady state: <= 1 blocking sync per sync_interval steps
    assert probe.syncs <= steps // probe.sync_interval
    assert probe.syncs >= 1


def test_steptime_probe_compute_bound_when_device_lags():
    class SlowReady:
        """block_until_ready on this sleeps — a device 30 ms behind."""
        def block_until_ready(self):
            time.sleep(0.030)
            return self
    probe = StepTimeProbe(sync_interval=2)
    for _ in range(8):
        probe.note_data_wait(0.0001)
        probe.record_step(dispatch_s=0.0005, ready=SlowReady())
    assert probe.verdict() == "compute-bound"


# -- JSONL logger -----------------------------------------------------------

def test_telemetry_logger_rotates(tmp_path):
    reg = MetricRegistry()
    reg.counter("t_log_total", "x").inc()
    path = str(tmp_path / "t.jsonl")
    lg = TelemetryLogger(path, interval_s=999, max_bytes=256,
                         registry=reg)
    for _ in range(6):
        lg.write_now()
    lg.stop()
    assert lg.rotations >= 1
    assert os.path.exists(path) and os.path.exists(path + ".1")
    for line in open(path):
        rec = json.loads(line)
        assert rec["metrics"]["t_log_total"] == 1.0


# -- profiler ---------------------------------------------------------------

def test_profiler_bracket_writes_nonempty_dump(tmp_path):
    import jax
    import jax.numpy as jnp
    dump = str(tmp_path / "prof")
    prof = StepProfiler("1-2", dump)
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    y = None
    for step in range(4):
        prof.maybe_start(step)
        y = f(jnp.ones((64,)) * step)
        prof.maybe_stop(step + 1, ready=y)
    prof.close(y)
    assert prof.done and not prof.active
    files = [os.path.join(dp, f) for dp, _dn, fn in os.walk(dump)
             for f in fn]
    assert files, "profiler dump directory is empty"
    assert sum(os.path.getsize(f) for f in files) > 0


def test_profiler_range_parsing():
    from cxxnet_tpu.telemetry.profiler import parse_step_range
    assert parse_step_range("3-7") == (3, 7)
    assert parse_step_range(" 5 ") == (5, 5)
    with pytest.raises(ValueError):
        parse_step_range("7-3")
    with pytest.raises(ValueError):
        parse_step_range("x-y")


# -- config knobs -----------------------------------------------------------

def test_parse_telemetry_config():
    from cxxnet_tpu.config import ConfigError, parse_telemetry_config
    tc = parse_telemetry_config([
        ("telemetry_trace", "/tmp/t.json"),
        ("telemetry_sync_interval", "16"),
        ("telemetry_port", "9090"),
        ("telemetry_profile_steps", "2-4"),
    ])
    assert tc.trace_path == "/tmp/t.json"
    assert tc.sync_interval == 16 and tc.port == 9090
    assert tc.profile_steps == "2-4" and tc.profile_dir  # default filled
    with pytest.raises(ConfigError):
        parse_telemetry_config([("telemetry_tracee", "x")])  # typo
    with pytest.raises(ConfigError):
        parse_telemetry_config([("telemetry_sync_interval", "0")])
    with pytest.raises(ConfigError):
        parse_telemetry_config([("telemetry_profile_steps", "9-1")])


# -- resilience counters are registry views ---------------------------------

def test_resilience_counters_registry_backed():
    from cxxnet_tpu.resilience import counters
    before = counters.get("test.telemetry_probe")
    counters.inc("test.telemetry_probe", 2)
    assert counters.get("test.telemetry_probe") == before + 2
    assert counters.snapshot()["test.telemetry_probe"] == before + 2
    # the SAME number must appear in a /metrics render under the
    # sanitized prometheus name — one store, two views
    text = render_prometheus(REGISTRY)
    assert f"cxxnet_test_telemetry_probe_total {before + 2}" in text


# -- ServingStats / statz key-compat (PR-1 contract) ------------------------

SNAPSHOT_KEYS = {
    "uptime_s", "requests", "qps", "latency_ms", "batches",
    "compile_cache",
}
REQUEST_KEYS = {"total", "ok", "rejected_backpressure",
                "rejected_deadline", "rejected_breaker", "failed"}
LATENCY_KEYS = {"p50", "p95", "p99", "mean", "samples"}
BATCH_KEYS = {"dispatched", "coalesced_ge2", "avg_requests_per_batch",
              "fill_ratio", "rows_real", "rows_padded"}
CACHE_KEYS = {"hits", "misses", "evictions", "size", "capacity"}


def test_serving_stats_snapshot_key_compat():
    from cxxnet_tpu.serve.stats import ServingStats
    st = ServingStats()
    st.record_request()
    st.record_done(0.005)
    st.record_batch(n_requests=2, rows_real=3, rows_bucket=4)
    st.record_cache(hit=False, size=1, capacity=8)
    st.record_cache(hit=True)
    st.record_reject("backpressure")
    st.record_reject("breaker")
    st.record_reject("deadline")
    st.record_failure()
    s = st.snapshot()
    assert set(s.keys()) == SNAPSHOT_KEYS
    assert set(s["requests"].keys()) == REQUEST_KEYS
    assert set(s["latency_ms"].keys()) == LATENCY_KEYS
    assert set(s["batches"].keys()) == BATCH_KEYS
    assert set(s["compile_cache"].keys()) == CACHE_KEYS
    assert s["requests"] == {"total": 1, "ok": 1,
                             "rejected_backpressure": 1,
                             "rejected_deadline": 1,
                             "rejected_breaker": 1, "failed": 1}
    assert s["batches"]["dispatched"] == 1
    assert s["batches"]["coalesced_ge2"] == 1
    assert s["batches"]["fill_ratio"] == 0.75
    assert s["compile_cache"] == {"hits": 1, "misses": 1, "evictions": 0,
                                  "size": 1, "capacity": 8}
    # per-instance isolation: a second stats object starts at zero even
    # though both live in the one process registry
    st2 = ServingStats()
    assert st2.snapshot()["requests"]["total"] == 0
    # and the registry carries the same numbers for scraping
    text = render_prometheus(REGISTRY)
    assert ('cxxnet_serve_requests_total{engine="%s",result="ok"} 1'
            % st.instance) in text
    assert st.log_line().startswith("serve[")


def test_two_stats_instances_do_not_share_series():
    from cxxnet_tpu.serve.stats import ServingStats
    a, b = ServingStats(), ServingStats()
    a.record_request()
    a.record_cache(hit=False, size=1, capacity=4)
    assert b.requests_total == 0 and b.cache_misses == 0
    assert a.requests_total == 1 and a.cache_misses == 1


# -- ThreadBufferIterator shutdown-hang regression --------------------------

class _EndlessIter:
    """Unbounded base iterator: without the timed put, its producer
    thread wedges in queue.put() the moment the consumer stops."""

    def __init__(self):
        self.produced = 0

    def before_first(self):
        pass

    def next(self):
        self.produced += 1
        from cxxnet_tpu.io.data import DataBatch
        return DataBatch(data=np.zeros((2, 1, 1, 4), np.float32),
                         label=np.zeros((2, 1), np.float32))


def _tb(base, buffer_size=1):
    from cxxnet_tpu.io.proc import ThreadBufferIterator
    it = ThreadBufferIterator([("buffer_size", str(buffer_size))], base)
    return it


def test_threadbuffer_teardown_does_not_hang():
    base = _EndlessIter()
    it = _tb(base, buffer_size=1)
    assert it.next() is not None
    # let the producer refill the queue and block in put()
    time.sleep(0.1)
    done = threading.Event()

    def reset():
        it.before_first()           # the call that used to hang forever
        done.set()
    t = threading.Thread(target=reset, daemon=True)
    t.start()
    t.join(timeout=10)
    assert done.is_set(), \
        "before_first() hung: producer stuck in a blocking queue.put"
    # the restarted producer serves fresh batches
    assert it.next() is not None
    it._stop.set()                  # leave no live producer behind


def test_threadbuffer_repeated_epochs_still_work():
    class Finite:
        def __init__(self, n):
            self.n = n
            self.i = 0

        def before_first(self):
            self.i = 0

        def next(self):
            from cxxnet_tpu.io.data import DataBatch
            if self.i >= self.n:
                return None
            self.i += 1
            return DataBatch(data=np.full((2, 1, 1, 4), self.i,
                                          np.float32),
                             label=np.zeros((2, 1), np.float32))
    it = _tb(Finite(5), buffer_size=2)
    for _epoch in range(3):
        it.before_first()
        seen = 0
        while it.next() is not None:
            seen += 1
        assert seen == 5
