"""Set-up in the program's own spans: the compile instrument's
``compile.trace`` / ``compile.lower`` / ``compile.backend`` spans and
counters, the task driver's ``setup.task`` / ``setup.weights`` /
``setup.input`` / ``train.round`` spans, what ``keep(())`` leaves out,
and the persistent cache's loads marked ``cached``."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from cxxnet_tpu.telemetry import anomaly
from cxxnet_tpu.telemetry.registry import REGISTRY
from cxxnet_tpu.telemetry.trace import TRACER

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("trace", "lower", "backend")


@pytest.fixture
def ring():
    assert anomaly.install_compile_counter() is True
    TRACER.disable()
    TRACER.clear()
    TRACER.keep(("setup",))
    yield TRACER
    TRACER.keep(())
    TRACER.clear()


def _compile_spans(fn_part):
    return [e for e in TRACER.events() if e["name"].startswith("compile.")
            and fn_part in e["args"]["fn"]]


def _seconds(phase):
    return REGISTRY.counter("cxxnet_compile_seconds_total",
                            labels=("phase",)).labels(phase).value


def test_a_first_call_records_three_compile_spans(ring):
    import jax
    import jax.numpy as jnp

    def toy_first_call(x):
        return jnp.tanh(x) * 3 + 1
    x = jnp.ones((7,))
    compiles = REGISTRY.counter("cxxnet_compiles_total")
    c0, s0 = compiles.value, {p: _seconds(p) for p in PHASES}
    jax.jit(toy_first_call)(x).block_until_ready()
    spans = _compile_spans("toy_first_call")
    assert sorted(e["name"] for e in spans) \
        == ["compile.backend", "compile.lower", "compile.trace"]
    assert all(e["cat"] == "setup" and e["dur"] >= 0 for e in spans)
    backend = next(e for e in spans if e["name"] == "compile.backend")
    assert backend["args"]["cached"] is False
    assert compiles.value >= c0 + 1
    assert all(_seconds(p) > s0[p] for p in PHASES)
    # trace, then lower, then the backend: in that order on the clock
    by = {e["name"]: e for e in spans}
    assert by["compile.trace"]["ts"] <= by["compile.lower"]["ts"] \
        <= by["compile.backend"]["ts"]
    # a second call finds the executable in jit's own cache
    jax.jit(toy_first_call)(x).block_until_ready()
    assert len(_compile_spans("toy_first_call")) == 3


def test_compile_spans_lie_inside_their_program_span(ring):
    import jax
    import jax.numpy as jnp

    def toy_nested(x):
        return jnp.cos(x) - 2
    with TRACER.span("setup.weights", cat="setup"):
        jax.jit(toy_nested)(jnp.ones((3,))).block_until_ready()
    outer = next(e for e in TRACER.events() if e["name"] == "setup.weights")
    spans = _compile_spans("toy_nested")
    assert len(spans) == 3
    for e in spans:
        assert e["tid"] == outer["tid"]
        # jax times with its own clock: a few microseconds of slack
        assert outer["ts"] - 50 <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"]


def test_nothing_kept_records_nothing_and_still_counts(ring):
    import jax
    import jax.numpy as jnp

    def toy_unkept(x):
        return jnp.sin(x) + 5
    ring.keep(())
    compiles = REGISTRY.counter("cxxnet_compiles_total")
    c0 = compiles.value
    jax.jit(toy_unkept)(jnp.ones((4,))).block_until_ready()
    assert ring.events() == []
    assert compiles.value >= c0 + 1


def test_one_compile_listener():
    """The instrument is installed once, whoever asks: the task driver,
    the compile cache, the telemetry session or a harness."""
    from jax._src import monitoring
    from cxxnet_tpu import compile_cache
    anomaly.install_compile_counter()
    before = len(monitoring.get_event_duration_listeners())
    n_events = len(monitoring.get_event_listeners())
    assert anomaly.install_compile_counter() is True
    assert len(monitoring.get_event_duration_listeners()) == before
    assert len(monitoring.get_event_listeners()) == n_events
    assert not hasattr(compile_cache, "_install_hit_listener")


def test_a_load_from_the_persistent_cache_is_marked_cached(tmp_path):
    """A fresh process with a compile cache of its own: the first call
    builds and stores, and after ``jax.clear_caches()`` the same call
    loads — ``cached: true`` and one more cache hit."""
    script = textwrap.dedent(f"""
        import json
        import jax
        import jax.numpy as jnp
        from cxxnet_tpu.compile_cache import enable_compile_cache
        from cxxnet_tpu.telemetry.anomaly import install_compile_counter
        from cxxnet_tpu.telemetry.registry import REGISTRY
        from cxxnet_tpu.telemetry.trace import TRACER
        install_compile_counter()
        TRACER.keep(("setup",))
        enable_compile_cache({str(tmp_path / "cache")!r})
        hits = REGISTRY.get("cxxnet_compile_cache_hits_total")

        def toy_cached(x):
            return jnp.exp(x) * 7
        out = []
        for _ in range(2):
            h0, n0 = hits.value, len(TRACER.events())
            jax.jit(toy_cached)(jnp.ones((9,))).block_until_ready()
            backend = [e["args"] for e in TRACER.events()[n0:]
                       if e["name"] == "compile.backend"]
            out.append([hits.value - h0,
                        sum(a["cached"] for a in backend),
                        [a["cached"] for a in backend
                         if "toy_cached" in a["fn"]]])
            jax.clear_caches()
        print(json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", script], cwd=_REPO, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    first, second = json.loads(p.stdout.strip().splitlines()[-1])
    assert first == [0, 0, [False]]
    # every load a hit, and the toy function's one of them
    hits, loads, toy = second
    assert hits == loads >= 1 and toy == [True]


# -- the task driver's set-up spans -------------------------------------------

_CFG = """
data = train
iter = synthetic
  num_inst = 64
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
"""


def _train(extra=""):
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.main import LearnTask
    TRACER.disable()
    TRACER.clear()
    try:
        LearnTask(parse_config_string(_CFG + extra)).run()
        return [e for e in TRACER.events() if e.get("cat") == "setup"]
    finally:
        TRACER.keep(())
        TRACER.clear()


def test_the_task_driver_records_its_set_up():
    evs = _train()
    names = [e["name"] for e in evs]
    for name in ("setup.task", "setup.weights", "setup.input"):
        assert name in names, name
    rounds = [e for e in evs if e["name"] == "train.round"]
    assert [e["args"]["round"] for e in rounds] == [0, 1]
    by = {n: next(e for e in evs if e["name"] == n)
          for n in ("setup.task", "setup.weights")}
    # LearnTask first, its weights after; the rounds after both
    end = lambda e: e["ts"] + e["dur"]
    assert end(by["setup.task"]) <= by["setup.weights"]["ts"]
    assert end(by["setup.weights"]) <= rounds[0]["ts"]
    # the step's first compiles are inside the first round, on its thread
    backend = [e for e in evs if e["name"] == "compile.backend"]
    assert backend
    r0 = rounds[0]
    assert any(e["tid"] == r0["tid"] and r0["ts"] <= e["ts"]
               and end(e) <= end(r0) for e in backend)
    # the chain's first batch is made once, inside the first round
    inputs = [e for e in evs if e["name"] == "setup.input"]
    assert len([e for e in inputs if e["ts"] >= r0["ts"]]) == 1


def test_steptime_zero_records_no_set_up_span():
    assert _train("telemetry_steptime = 0\n") == []
