#!/usr/bin/env python3
"""benchmarks/run.py — one run of one cell of BENCHMARK.json.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

ONE process at a time touches JAX (a chip belongs to one process): the
command itself only supervises, and the run is its child. Where the
child's set-up BUILT executables (the persistent compile cache was
cold), it stops before the window, the cache now holding them, and the
run is made once more in a fresh child that loads them: a process that
has compiled its step trains more slowly on a mesh for the rest of its
life (0.6-1.7 % in ``ibn_dp4``; PERF.md, PR 27), so a checkout's first
run would differ from every later one in more than ``setup_s``.
``setup_s`` counts from the command's start, through both children. The
child builds the program's own
``LearnTask`` from the cell's configuration file, opens the cell's feed,
runs a short warm-up round, has the configuration's own reference check
the program, and then runs ONE measured round **through the program's own
round loop** (``LearnTask._train_rounds`` -> ``prefetch_device`` ->
``_timed_batches`` -> ``Trainer.update``; probe, sentinel, ``print_step``
at their defaults), and prints one JSON object as its last line:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, when
traced ``breakdown``, and last ``compared``: every number that decided
``correct`` beside its limit (on standard error too, as its last line).
With ``--trace 0`` the metrics are the cell's end-to-end metrics and no
profiler runs; with ``--trace 1`` the last seconds of the window are
profiled (the device tracer only), the dump stays under
``benchmarks/.cache/trace/<cell>``, and the metrics are the cell's
per-layer metrics: those on the host's clock from the window before the
profiler, those of the device from the trace.

Driven by data: this file holds no list of cells, configurations,
traffic mixes or metrics, and knows no layer kind, no loss shape, no
tolerance and no item size. It finds

* the cell, its configuration and the metrics it reports in the
  manifest (``BENCHMARK.json`` at the root, or ``--manifest``);
* the traffic mix in ``traffic/<traffic>.json``, whose ``feed`` names
  ``feeds/<feed>.py``;
* the configuration's plain reference in ``references/<reference>.py``,
  named by the configuration file's ``reference``;
* each per-layer metric's reader in ``layer_metrics/<metric>.py``

under the manifest's own ``paths`` first and this directory second, so a
later PR adds a cell, a configuration, a mix, a feed, a reference or a
metric as files plus manifest entries, editing nothing that is here.

What each kind of file is held to:

* a configuration file (JSON): ``net.conf`` (the program's conf beside
  it), ``overrides``, ``input_shape``, ``item`` and whatever its feed and
  its reference read; its optional keys and what each is when left out
  are in ``config_defaults.json`` (``reference``, ``check``,
  ``items_per_row``: the items a row of a batch trains, checked against
  the loop's first batch before the window — for ``"item": "token"`` it
  is the label's width, otherwise 1 — so that ``train_items_per_s_chip``
  counts images for a convnet and positions for a sequence model);
* a feed: ``section(traffic, ctx) -> str`` (the conf's data section) and
  ``open(task, tr, traffic, ctx)`` -> an object with ``batches()`` (an
  endless stream), ``close()`` and, where one batch is repeated,
  ``one_batch = True``. ``ctx`` holds ``seed``, ``chips``, ``rows``,
  ``root``, ``cache_dir``, ``say``, ``input_shape``, ``num_class`` (when
  the file has it) and ``config``: the whole configuration file;
* a reference module — the plain reference, which imports nothing of the
  program — with
  ``check(kind, view) -> (ok, said)``: the reference's part of
  ``correct``. ``kind`` is the configuration's ``check``; ``view`` holds
  ``config``, ``layers`` and ``defaults`` (the parsed layers and global
  pairs), ``trainer``, ``params0`` (a copy of the initial weights, only
  when the module's optional ``needs_initial_params(kind)`` says so: the
  step donates its arguments), ``batch0`` (the loop's first batch),
  ``warm_losses`` (the warm-up round's), ``dtype`` (the compute dtype),
  ``rows``, ``chips`` and ``say``. ``said`` is printed: every number
  compared beside its limit;
  ``train_step_flops(view) -> float``: the model operations of one step
  at ``rows`` rows, from shapes, nothing run. It becomes
  ``view["step_flops"]`` of the readers.
  A module that lacks either is a non-zero exit that names it;
* a reader: ``read(view) -> float | None`` (``None``: nothing to read,
  the metric is left out of the line).

A platform other than ``tpu``, or fewer devices than the cell's
``chips``, is a non-zero exit with no result line. ``--rehearse-cpu``
walks the same code on the CPU backend for a manifest of toy cells (the
one under ``tests/benchmarks/data/toy``); it refuses the root manifest,
and its last line names ``cpu`` and carries no device-derived metric.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()        # the supervisor's starts set-up

import argparse
import importlib.util
import json
import math
import os
import shutil
import signal
import subprocess
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
#: program seeds are 31-bit: ``--seed`` may be a little over 2**31
_SEED_MOD = 2 ** 31 - 1
#: steps the warm-up round takes through the loop and the feed (the
#: second update() of a process is a one-off of 0.4-1.2 s, PERF.md)
WARMUP_STEPS = 6
#: a traced run profiles the last seconds of its window (its second
#: half, if that is shorter); the host-clock metrics are read from the
#: part before them, where no profiler runs
TRACE_SECONDS = 3.0
#: a child's exit code for "my set-up built executables, which the
#: persistent compile cache now holds: run me again in a fresh process"
RUN_AGAIN = 75


class BuiltInSetup(Exception):
    """The first child's way out before the window, through ``finally``."""


def say(**fields) -> None:
    """An earlier line: anything worth reading that is not the result."""
    print(json.dumps(fields), flush=True)


def die(why: str, code: int = 2):
    print(f"benchmarks/run.py: {why}", file=sys.stderr, flush=True)
    sys.exit(code)


# -- finding things by name ------------------------------------------------


def search_dirs(manifest_path: str, manifest: dict):
    base = os.path.dirname(os.path.abspath(manifest_path))
    dirs = [os.path.normpath(os.path.join(base, p))
            for p in manifest.get("paths", [])]
    return dirs + [_HERE]


def find_file(dirs, *parts) -> str:
    for d in dirs:
        path = os.path.join(d, *parts)
        if os.path.isfile(path):
            return path
    die(f"no {os.path.join(*parts)} under any of {dirs}")


def load_module(path: str):
    name = "bench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    die(f"the manifest has no {what} named {name!r}")


def reported(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


# -- host spans -----------------------------------------------------------


class Spans:
    """The benchmark's own spans around the calls into each layer, kept
    in memory on the host clock."""

    def __init__(self):
        self.events = []            # (name, t0, t1) perf_counter seconds

    def span(self, name):
        return _Span(self, name)


class _Span:
    def __init__(self, owner, name):
        self.owner, self.name = owner, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.owner.events.append((self.name, self.t0, time.perf_counter()))
        return False


class Profiler:
    """Brackets the last seconds of the window with jax.profiler: the
    device tracer only. With the host tracer on (at level 1 or 2) the
    runtime writes one event per inner call of the host-side re-tiling
    of every batch it copies to the device — 0.9 M events and 1.85 s a
    batch in ``ibn_records`` against milliseconds (PERF.md, PR 23) — so
    the traced run measured the profiler. The host spans reach the
    trace's clock through the dump's own ``profile_start_time``
    (``place`` below) instead of ``TraceAnnotation``s."""

    def __init__(self, dump_dir):
        self.dir = dump_dir
        self.t_start = self.unix_ns_at_start = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # 18k python events per second
        opts.host_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.unix_ns_at_start = time.time_ns()
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def place(self, events, profile_start_unix_ns):
        """The spans as events on the trace's clock (ns from the start
        of the profile)."""
        from benchmarks.trace_reduce import Ev
        if profile_start_unix_ns is None:
            return []
        shift = self.unix_ns_at_start - profile_start_unix_ns
        return sorted((Ev(name, "", shift + int((t0 - self.t_start) * 1e9),
                          int((t1 - t0) * 1e9)) for name, t0, t1 in events),
                      key=lambda e: e.start)


class Round:
    """The iterator handed to the program's loop for one round: counts
    the batches, times each fetch from the feed, and ends the epoch at
    the deadline. The clock starts at the loop's first fetch."""

    def __init__(self, batches, spans, seconds=None, steps=None,
                 profiler=None):
        self.batches, self.spans = iter(batches), spans
        self.seconds, self.steps, self.profiler = seconds, steps, profiler
        self.t_first = None
        self.handed = 0
        self.first_batch = None

    def __iter__(self):
        return self

    def __next__(self):
        now = time.perf_counter()
        if self.t_first is None:
            self.t_first = now
        if self.steps is not None and self.handed >= self.steps:
            raise StopIteration
        if self.seconds is not None \
                and now - self.t_first >= self.seconds:
            raise StopIteration
        p = self.profiler
        if p is not None and p.t_start is None and now - self.t_first \
                >= max(self.seconds - TRACE_SECONDS, self.seconds / 2):
            with self.spans.span("profiler"):
                p.start()       # stopped after the loop has returned
        with self.spans.span("fetch"):
            batch = next(self.batches)
        if self.first_batch is None:
            self.first_batch = batch
        self.handed += 1
        return batch


def wrap_update(tr, spans, handles):
    """Span around ``Trainer.update`` (the bound method, wrapped on the
    instance: no program edit) and the loss of every step kept as a
    device value, fetched only after the window. With ``eval_train`` on
    (the default) ``update`` ends in a drain of the previous step's
    train metric, a host fetch that waits for the device: it gets a
    span of its own, nested in the first, so that the enqueue can be
    told from the wait. Returns whether the drain was found."""
    inner = tr.update

    def update(batch):
        with spans.span("update"):
            inner(batch)
        handles.append(tr.last_loss_handle)
    tr.update = update
    drain = getattr(tr, "_drain_pending_metric", None)
    if drain is None:
        return False

    def drain_pending_metric():
        with spans.span("metric_drain"):
            return drain()
    tr._drain_pending_metric = drain_pending_metric
    return True


def wrap_probe(task, spans):
    """Span around the step-time probe's ``record_step``, where the
    loop syncs with the device every ``telemetry_sync_interval`` steps:
    an idle gap that falls there is named for it."""
    make = task.telemetry.make_probe

    def make_probe():
        probe = make()
        inner = probe.record_step

        def record_step(*a, **kw):
            with spans.span("probe_sync"):
                return inner(*a, **kw)
        probe.record_step = record_step
        return probe
    task.telemetry.make_probe = make_probe


def run_round(task, tr, rnd: Round, round_no: int) -> float:
    """One round of the program's own loop over ``rnd``; returns the
    clock at a value fetch of the last step's loss."""
    task.start_counter, task.num_round = round_no, round_no + 1
    task._train_rounds(tr, rnd, [])
    tr.last_loss                        # float(): the barrier
    return time.perf_counter()


# -- the command: a supervisor that never touches JAX ---------------------------
def _die_with_parent():
    import ctypes
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)      # PR_SET_PDEATHSIG


def supervise(argv) -> int:
    """Runs the run as a child, and once more where the first child says
    that its set-up built what the compile cache now holds. The child
    writes to this process's own standard output and error; it is ended
    with this process, and waited for, on every path out."""
    code = 1
    for attempt in (1, 2):
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), *argv,
             "--attempt", str(attempt), "--started-at", repr(_T_PROCESS)],
            preexec_fn=_die_with_parent)
        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, lambda *_: proc.terminate())
        try:
            code = proc.wait()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if code != RUN_AGAIN:
            break
    return code if code >= 0 else 128 - code


# -- the run ------------------------------------------------------------------


def build_pairs(cfg_file: dict, cfg_path: str, dev: str, seed: int,
                rows: int, data_section: str, model_dir: str):
    from cxxnet_tpu.config import parse_config_string
    conf_path = os.path.join(os.path.dirname(cfg_path),
                             cfg_file["net"]["conf"])
    with open(conf_path) as f:
        text = f.read()
    text += "\n" + "\n".join(cfg_file.get("overrides", [])) + "\n"
    return parse_config_string(data_section + text) + [
        ("batch_size", str(rows)), ("dev", dev), ("seed", str(seed)),
        ("model_dir", model_dir),
        # the window is one round with no save in it: a save is a cell
        # of its own (PERF.md, Open questions A)
        ("save_model", "0")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--manifest",
                    default=os.path.join(_ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="CPU backend, toy manifests only")
    # the supervisor's to its children: which of the two, and its start
    ap.add_argument("--attempt", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--started-at", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not args.attempt:
        return supervise(sys.argv[1:] if argv is None else list(argv))
    # set-up is counted from the supervisor's start: perf_counter is the
    # machine's monotonic clock, one for every process
    t_command = args.started_at

    sys.path.insert(0, _ROOT)
    with open(args.manifest) as f:
        manifest = json.load(f)
    is_root = os.path.abspath(args.manifest) == os.path.join(
        _ROOT, "BENCHMARK.json")
    if args.rehearse_cpu and is_root:
        die("--rehearse-cpu is for toy manifests: a cell of the root "
            "BENCHMARK.json runs on the chip or not at all")
    dirs = search_dirs(args.manifest, manifest)
    cell = named(manifest["workloads"], args.workload, "workload")
    cfg_entry = named(manifest["configs"], cell["config"], "config")
    cfg_path = os.path.join(os.path.dirname(os.path.abspath(args.manifest)),
                            cfg_entry["file"])
    with open(os.path.join(_HERE, "config_defaults.json")) as f:
        cfg_file = json.load(f)["optional_keys"]
    with open(cfg_path) as f:
        cfg_file.update(json.load(f))
    with open(find_file(dirs, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    feed_mod = load_module(find_file(dirs, "feeds",
                                     traffic["feed"] + ".py"))
    chips = int(cell["chips"])

    # libtpu would otherwise log under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
        if jax.config.jax_num_cpu_devices < chips:
            jax.config.update("jax_num_cpu_devices", chips)
    devs = jax.devices()
    platform = devs[0].platform
    want = "cpu" if args.rehearse_cpu else "tpu"
    if platform != want:
        die(f"JAX found platform {platform!r} ({devs[0].device_kind} "
            f"x{len(devs)}), not {want!r}: nothing was run")
    if len(devs) < chips:
        die(f"cell {cell['name']} needs {chips} chip(s), JAX found "
            f"{len(devs)}")
    import numpy as np
    from benchmarks import flops, trace_reduce
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.ops.fused import selection_counts
    from cxxnet_tpu.telemetry.anomaly import install_compile_counter
    from cxxnet_tpu.telemetry.registry import REGISTRY
    ref_path = find_file(dirs, "references", cfg_file["reference"] + ".py")
    ref_mod = load_module(ref_path)
    for fn in ("check", "train_step_flops"):
        if not callable(getattr(ref_mod, fn, None)):
            die(f"the reference module {ref_path} has no {fn}()")

    seed = args.seed % _SEED_MOD
    rows = int(traffic["rows_per_chip"]) * chips
    cache_dir = os.path.join(_HERE, ".cache")
    ctx = {"seed": seed, "chips": chips, "rows": rows, "root": _ROOT,
           "input_shape": tuple(cfg_file["input_shape"]),
           "config": cfg_file, "cache_dir": cache_dir, "say": say}
    if "num_class" in cfg_file:
        ctx["num_class"] = int(cfg_file["num_class"])
    dev = f"{platform}:0" if chips == 1 else f"{platform}:0-{chips - 1}"
    pairs = build_pairs(cfg_file, cfg_path, dev, seed, rows,
                        feed_mod.section(traffic, ctx),
                        os.path.join(cache_dir, "models"))
    install_compile_counter()
    compiles = REGISTRY.get("cxxnet_compiles_total")
    marks = [("before_this_process", _T_PROCESS),
             ("imports_and_data", time.perf_counter())]
    task = LearnTask(pairs)      # enables the compile cache by its rule
    tr = task.trainer
    task._init_model()
    marks.append(("learn_task_and_weights", time.perf_counter()))
    from cxxnet_tpu.compile_cache import cache_dir as compile_cache_dir
    say(workload=cell["name"], config=cfg_entry["name"],
        traffic=traffic["name"], seed=args.seed, program_seed=seed,
        rows=rows, dev=dev, platform=platform, kind=devs[0].device_kind,
        devices=len(devs), cpu_count=os.cpu_count(),
        compile_cache=compile_cache_dir(),
        compute_dtype=tr.policy.compute_name, jax=jax.__version__)
    if chips > 1 and tr.mesh.data_parallel != chips:
        die(f"mesh is not dp={chips}: {dict(tr.mesh.mesh.shape)}", 1)

    spans, handles = Spans(), []
    say(metric_drain_span=wrap_update(tr, spans, handles))
    wrap_probe(task, spans)
    feed = feed_mod.open(task, tr, traffic, ctx)
    marks.append(("feed_open", time.perf_counter()))
    ref_view = {"config": cfg_file, "layers": tr.graph.layers,
                "defaults": dict(tr.graph.defcfg), "trainer": tr,
                "params0": None, "dtype": tr.policy.compute_name,
                "rows": rows, "chips": chips, "say": say}
    wants_params0 = getattr(ref_mod, "needs_initial_params", None)
    if wants_params0 is not None and wants_params0(cfg_file["check"]):
        # the step donates its arguments: keep the initial weights for
        # the reference, which sees the loop's first batch afterwards
        import jax.numpy as jnp
        ref_view["params0"] = jax.tree_util.tree_map(jnp.copy, tr.params)
    try:
        # -- warm-up: a short first round through the same loop and feed
        warm = Round(feed.batches(), spans, steps=WARMUP_STEPS)
        marks.append(("warmup_round", run_round(task, tr, warm, 0)))
        warm_losses = [float(v) for v in jax.device_get(handles)]
        del handles[:]
        ref_view.update(batch0=warm.first_batch, warm_losses=warm_losses)
        ok_ref, ref_said = ref_mod.check(cfg_file["check"], ref_view)
        ok_ref = bool(ok_ref)
        ref_view["params0"] = None
        say(**ref_said, ok=ok_ref)
        marks.append(("reference_check", time.perf_counter()))
        # what one row of a batch counts for, against the batch itself
        first = warm.first_batch
        seen = first.label if first.host_label is None else first.host_label
        per_row = int(cfg_file["items_per_row"])
        in_row = int(np.shape(seen)[-1]) if cfg_file["item"] == "token" else 1
        if per_row != in_row:
            die(f"{cfg_path} says items_per_row {per_row}, but a row of "
                f"the first batch holds {in_row} {cfg_file['item']}(s)", 1)
        ok_warm = all(math.isfinite(v) for v in warm_losses)
        # one batch, repeated: the loss has to fall
        has_to_fall = bool(getattr(feed, "one_batch", False))
        if has_to_fall:
            ok_warm = ok_warm and warm_losses[-1] < warm_losses[0]
        by = selection_counts(tr.net.fused_log)
        say(warmup_losses=warm_losses, ok=ok_warm,
            fused_kernels={k: dict(c) for k, c in by.items()})
        # what this set-up built, and did not load: the program's count of
        # backend compiles, which wraps the cached path too, less its count
        # of persistent-cache hits. Where the cache is on, a fresh process
        # finds them there, and the window is its to run
        hits = REGISTRY.get("cxxnet_compile_cache_hits_total")
        built = int(compiles.value - (hits.value if hits else 0))
        say(built_in_setup=built, attempt=args.attempt)
        if built and args.attempt == 1 and compile_cache_dir():
            raise BuiltInSetup
        # -- the window: one round of the program's own loop
        profiler = Profiler(os.path.join(cache_dir, "trace",
                                         cell["name"])) \
            if args.trace else None
        del spans.events[:]
        c0 = compiles.value
        rnd = Round(feed.batches(), spans, seconds=args.seconds,
                    profiler=profiler)
        setup_s = time.perf_counter() - t_command
        say(setup_split_s={name: t - t0 for (name, t), t0 in zip(
            marks, [t_command] + [t for _, t in marks])})
        t_end = run_round(task, tr, rnd, 1)
        if profiler is not None and profiler.t_start is not None:
            profiler.stop()
        window_s = t_end - rnd.t_first
        n_compiles = int(compiles.value - c0)
        losses = np.asarray(jax.device_get(handles), np.float64)
    except BuiltInSetup:
        return RUN_AGAIN
    finally:
        feed.close()
        task.telemetry.close()
    steps = len(losses)
    failed = int(np.sum(~np.isfinite(losses)))
    items = rows * per_row * (steps - failed)
    correct = bool(ok_ref and ok_warm and failed == 0 and steps > 0)
    # where the host was in the window's slowest steps: a stall shows
    # here as one long period, named by the span that filled it
    ups = [e for e in spans.events if e[0] == "update"]
    periods = np.diff([u[1] for u in ups])
    say(slowest_step_periods=[
        {"step": int(i), "period_s": float(periods[i]),
         "update_s": ups[i][2] - ups[i][1],
         "others": {e[0]: e[2] - e[1] for e in spans.events
                    if e[0] != "update"
                    and ups[i][1] <= e[1] < ups[i + 1][1]}}
        for i in np.argsort(-periods)[:5]],
        period_s_p10_p50_p90_mean=[float(v) for v in (
            *np.percentile(periods, (10, 50, 90)), periods.mean())]
        if len(periods) else None,
        fetch_s=sum(e[2] - e[1] for e in spans.events if e[0] == "fetch"))
    say(steps=steps, items=items, window_s=window_s,
        first_loss=float(losses[0]) if steps else None,
        last_loss=float(losses[-1]) if steps else None,
        compiles_in_window=n_compiles, setup_s=setup_s)

    device_memory = [d.memory_stats() or {} for d in devs[:chips]]
    say(memory_stats=device_memory[0])
    # the runtime counts live buffers (``in_use``) apart from what it
    # reserves for the executables' temporaries (``reserved``): the
    # step's 8.3 GiB of temporaries are in the second only (PERF.md)
    peak = max(m.get("peak_bytes_in_use", 0)
               + m.get("peak_bytes_reserved", 0) for m in device_memory)
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": steps, "failed": failed}
    if not args.trace:
        values = {"train_items_per_s_chip": items / window_s / chips,
                  "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in manifest["end_to_end"]
            if reported(m, cell["name"]) and m["name"] in values}
    else:
        reduced = None
        if profiler.t_start is not None and platform != "cpu":
            xplane = trace_reduce.find_xplane(profiler.dir)
            # which fusions hold a convolution is in the step's compiled
            # text, not in the trace (served from jit's own caches); kept
            # beside the dump for whoever cuts a test fixture from it
            mxu_calls = trace_reduce.mxu_computations(
                tr.lower_train_step(warm.first_batch).compile().as_text())
            with open(os.path.join(profiler.dir, "mxu_calls.json"),
                      "w") as f:
                json.dump(sorted(mxu_calls), f)
            trace = trace_reduce.read(xplane, mxu_calls)
            host = profiler.place(spans.events, trace["start_unix_ns"])
            per_dev = [r for r in map(trace_reduce.reduce_device,
                                      trace["devices"].values())
                       if r is not None]
            if per_dev:
                reduced = {"devices": per_dev, "host": host}
                device["busy_s"] = float(np.mean(
                    [r["busy_s"] for r in per_dev]))
                device["window_s"] = float(np.mean(
                    [r["window_s"] for r in per_dev]))
                result["breakdown"] = trace_reduce.breakdown(
                    per_dev[0], host)
                first = per_dev[0]
                say(traced_steps=first["steps"],
                    ms_per_step_by_class={
                        k: 1e3 * v / first["steps"] for k, (v, _) in sorted(
                            first["by_cat"].items(),
                            key=lambda kv: -kv[1][0])[:16]},
                    top_instructions_ms_per_step=[
                        [k, 1e3 * v / first["steps"]] for k, v in
                        trace_reduce.top_instructions(first)])
        # the host-clock metrics are read before the profiler starts
        # (over the whole window where it never did): it is not there in
        # the runs whose rate they explain
        lo = rnd.t_first
        hi = profiler.t_start if profiler.t_start else t_end
        view = {"spans": [e for e in spans.events if lo <= e[1] < hi],
                "span_window_s": hi - lo,
                "compiles_in_window": n_compiles,
                "trace": reduced,
                # the configuration's own count, from shapes: nothing runs
                "step_flops": float(ref_mod.train_step_flops(ref_view)),
                "rows": rows, "chips": chips,
                "peaks": flops.chip_peaks(devs[0].device_kind)
                if platform != "cpu" else None}
        metrics = {}
        for m in manifest["per_layer"]:
            if not reported(m, cell["name"]):
                continue
            reader = load_module(find_file(dirs, "layer_metrics",
                                           m["name"] + ".py"))
            value = reader.read(view)
            if value is not None:    # nothing to read: left out
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["metrics"] = metrics
    result["device"] = device
    # every number compared, beside its limit: last in the line, and the
    # last thing on standard error
    result["compared"] = compared = dict(
        ref_said, warmup_loss_first=warm_losses[0],
        warmup_loss_last=warm_losses[-1],
        warmup_loss_has_to_fall=has_to_fall,
        nonfinite_steps=failed, nonfinite_steps_limit=0)
    print(json.dumps(result), flush=True)
    print("compared: " + json.dumps(compared), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
