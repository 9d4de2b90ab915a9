"""graftlint: golden fixture per pass + suppression/baseline mechanics
+ the whole-repo zero-unsuppressed-findings gate (doc/tasks.md "Static
analysis").

Each pass gets a minimal fixture proving (a) the violation is
detected, (b) an inline suppression WITH a reason silences it, and the
shared mechanics tests prove (c) a reason-less suppression is itself a
finding and (d) the baseline file absorbs accepted findings across
line drift. The repo gate at the bottom is the tier-1 contract:
``python tools/graftlint.py --all`` must exit 0, forever.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from cxxnet_tpu.analysis import (default_passes, load_baseline,
                                 pass_names, run_analysis,
                                 write_baseline, Project)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the CLI's --all surface, mirrored here so gate and CLI can't drift
LINT_PATHS = ("cxxnet_tpu", "tools", "tests")
CONTEXT_PATHS = ("bench.py", "__graft_entry__.py", "examples", "wrapper")


def lint(tmp_path, files, select=None, baseline=None):
    """Write a fixture project and run the analysis over it."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    proj = Project.load(str(tmp_path), sorted(files))
    passes = default_passes()
    if select:
        passes = [p for p in passes if p.name in select]
    return run_analysis(proj, passes, baseline=baseline,
                        known_pass_names=set(pass_names()))


def names(result):
    return [f.pass_name for f in result.findings]


# -- trace-purity -------------------------------------------------------------

_PURITY_BAD = """\
    import time
    import jax

    def helper(x):
        return x * time.time()          # impure, reached via closure

    def step(x):
        print("tracing")
        return helper(x) + x.item()

    f = jax.jit(step)
    """


def test_trace_purity_detects(tmp_path):
    r = lint(tmp_path, {"mod.py": _PURITY_BAD}, select=["trace-purity"])
    msgs = [f.message for f in r.findings]
    assert len(r.findings) == 3
    assert any("time.time" in m for m in msgs)          # via closure
    assert any("print()" in m for m in msgs)
    assert any(".item()" in m for m in msgs)
    # clickable anchors: every finding carries the flagged line
    assert all(f.line > 0 and f.path == "mod.py" for f in r.findings)


def test_trace_purity_ignores_untraced(tmp_path):
    src = """\
    import time
    import jax

    def host_loop(x):
        return time.time()              # never traced: fine

    def step(x):
        def host_cb(v):
            print(v, time.time())       # nested, never called from the
            return v                    # traced body: runs on the host
        return x * 2

    f = jax.jit(step)
    """
    r = lint(tmp_path, {"mod.py": src}, select=["trace-purity"])
    assert r.findings == []


def test_trace_purity_suppression(tmp_path):
    src = _PURITY_BAD.replace(
        'print("tracing")',
        'print("tracing")  # graftlint: disable=trace-purity '
        "(trace-time banner, fires once per compile by design)")
    r = lint(tmp_path, {"mod.py": src}, select=["trace-purity"])
    assert len(r.findings) == 2                 # print one suppressed
    assert len(r.suppressed) == 1
    assert r.suppressed[0].message.startswith("print()")


# -- atomic-io ----------------------------------------------------------------

_DURABLE_BAD = """\
    import os

    def save(path, data):
        with open(path, "wb") as f:
            f.write(data)
        os.rename(path + ".tmp", path)
    """


def test_atomic_io_detects_in_durable_module(tmp_path):
    r = lint(tmp_path,
             {"cxxnet_tpu/elastic/coord.py": _DURABLE_BAD},
             select=["atomic-io"])
    msgs = [f.message for f in r.findings]
    assert len(r.findings) == 2
    assert any("write_bytes_atomic" in m for m in msgs)
    assert any("os.rename" in m for m in msgs)


def test_atomic_io_scope_and_append_rule(tmp_path):
    ledger = """\
    def event(path, line):
        with open(path, "a") as f:       # sanctioned O_APPEND protocol
            f.write(line)
    """
    r = lint(tmp_path, {
        # not a durable module: same code, out of scope
        "cxxnet_tpu/io/writer.py": _DURABLE_BAD,
        "cxxnet_tpu/telemetry/ledger.py": ledger,
    }, select=["atomic-io"])
    assert r.findings == []
    # ...but a durable append OUTSIDE the ledger is flagged
    r2 = lint(tmp_path, {"cxxnet_tpu/elastic/hb.py": ledger},
              select=["atomic-io"])
    assert len(r2.findings) == 1
    assert "O_APPEND protocol" in r2.findings[0].message


def test_atomic_io_suppression(tmp_path):
    src = _DURABLE_BAD.replace(
        'with open(path, "wb") as f:',
        'with open(path, "wb") as f:  # graftlint: disable=atomic-io '
        "(scratch file on local tmpfs, rebuilt on restart)")
    r = lint(tmp_path, {"cxxnet_tpu/elastic/coord.py": src},
             select=["atomic-io"])
    assert len(r.findings) == 1          # os.rename still flagged
    assert len(r.suppressed) == 1


# -- signal-safety ------------------------------------------------------------

_SIGNAL_BAD = """\
    import signal

    def handler(signum, frame):
        prev = signal.getsignal(signal.SIGTERM)
        with open("/tmp/x", "w") as f:
            f.write("dying")

    signal.signal(signal.SIGTERM, handler)
    """


def test_signal_safety_detects(tmp_path):
    r = lint(tmp_path, {"mod.py": _SIGNAL_BAD},
             select=["signal-safety"])
    msgs = [f.message for f in r.findings]
    assert any("getsignal" in m and "bind-at-install" in m
               for m in msgs)
    assert any("context manager" in m for m in msgs)
    assert any("open()" in m for m in msgs)


def test_signal_safety_allows_event_set_and_prebound_chain(tmp_path):
    src = """\
    import signal
    import threading

    EVT = threading.Event()

    def install(prev_bound, chain):
        def handler(signum, frame):
            EVT.set()
            chain(signum, prev_bound)    # resolved at install time
        signal.signal(signal.SIGTERM, handler)
    """
    r = lint(tmp_path, {"mod.py": src}, select=["signal-safety"])
    assert r.findings == []


def test_signal_safety_suppression(tmp_path):
    src = _SIGNAL_BAD.replace(
        'prev = signal.getsignal(signal.SIGTERM)',
        'prev = signal.getsignal(signal.SIGTERM)  '
        "# graftlint: disable=signal-safety (single-installer tool "
        "script, no later installers to race)")
    r = lint(tmp_path, {"mod.py": src}, select=["signal-safety"])
    assert len(r.suppressed) == 1
    assert all("getsignal" not in f.message for f in r.findings)


# -- thread-shutdown ----------------------------------------------------------

def test_thread_shutdown_detects(tmp_path):
    src = """\
    import os
    import threading

    def fire_and_forget():
        t = threading.Thread(target=work)
        t.start()
        # a path join is NOT a thread join — must not satisfy the check
        return os.path.join("a", "b")

    def work():
        pass
    """
    r = lint(tmp_path, {"mod.py": src}, select=["thread-shutdown"])
    assert names(r) == ["thread-shutdown"]


def test_thread_shutdown_accepts_cleanup_idioms(tmp_path):
    src = """\
    import threading

    def daemonized():
        threading.Thread(target=work, daemon=True).start()

    def joined():
        ts = [threading.Thread(target=work) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    class Owner:
        def start(self):
            self._thread = threading.Thread(target=work)
            self._thread.start()

        def stop(self):
            self._thread.join(timeout=5)

    def work():
        pass
    """
    r = lint(tmp_path, {"mod.py": src}, select=["thread-shutdown"])
    assert r.findings == []


def test_thread_shutdown_suppression(tmp_path):
    src = """\
    import threading

    def fire_and_forget():
        # graftlint: disable=thread-shutdown (process-lifetime worker)
        t = threading.Thread(target=work)
        t.start()

    def work():
        pass
    """
    # note: suppression comment sits on the line ABOVE the ctor
    r = lint(tmp_path, {"mod.py": src}, select=["thread-shutdown"])
    assert r.findings == [] and len(r.suppressed) == 1


# -- config-namespace ---------------------------------------------------------

_NS_DECL = """\
    def parse_serve_config(cfg):
        known = {"serve_port": ("port", int),
                 "serve_replicas": ("replicas", int)}
        return known
    """

_NS_EVENTS = """\
    KNOWN_EVENTS = ("serve_start", "elastic_join")
    """


def test_config_namespace_detects_typo(tmp_path):
    src = """\
    def route(cfg):
        return cfg.get("serve_replicsa", 1)
    """
    r = lint(tmp_path, {"config.py": _NS_DECL, "mod.py": src},
             select=["config-namespace"])
    assert names(r) == ["config-namespace"]
    # graftlint: disable=config-namespace (the typo IS this fixture)
    assert "serve_replicsa" in r.findings[0].message


def test_config_namespace_exemptions(tmp_path):
    src = """\
    import pytest

    def ok(cfg, name):
        a = cfg["serve_port"]                  # declared
        b = cfg.get("serve_start")             # ledger event name
        c = name.startswith("serve_")          # bare prefix
        with pytest.raises(ValueError):
            cfg.check({"k": cfg["serve_oops"]})  # proving-the-raise
        return a, b, c
    """
    r = lint(tmp_path, {"config.py": _NS_DECL,
                        "ledger.py": _NS_EVENTS, "mod.py": src},
             select=["config-namespace"])
    assert r.findings == []


def test_config_namespace_suppression(tmp_path):
    src = """\
    def probe(cfg):
        return cfg.get("serve_legacy_knob")  # graftlint: disable=config-namespace (compat shim for pre-rename configs)
    """
    r = lint(tmp_path, {"config.py": _NS_DECL, "mod.py": src},
             select=["config-namespace"])
    assert r.findings == [] and len(r.suppressed) == 1


# -- dead-symbol --------------------------------------------------------------

def test_dead_symbol_detects(tmp_path):
    src = """\
    def used():
        return 1

    def orphan():
        return used()
    """
    user = """\
    from cxxnet_tpu.mod import used
    print(used())
    """
    r = lint(tmp_path, {"cxxnet_tpu/mod.py": src,
                        "tools/user.py": user},
             select=["dead-symbol"])
    assert names(r) == ["dead-symbol"]
    assert "'orphan'" in r.findings[0].message


def test_dead_symbol_exemptions(tmp_path):
    src = """\
    def exported_api():
        return 1

    @register_thing("name")
    def registered():
        return 2

    def register_thing(name):
        def deco(fn):
            return fn
        return deco
    """
    init = """\
    from .mod import exported_api
    """
    r = lint(tmp_path, {"cxxnet_tpu/mod.py": src,
                        "cxxnet_tpu/__init__.py": init},
             select=["dead-symbol"])
    assert r.findings == []


def test_dead_symbol_suppression(tmp_path):
    src = """\
    # graftlint: disable-file=dead-symbol (exercised via ctypes from the C demo, invisible to the AST)
    def c_entry():
        return 1
    """
    r = lint(tmp_path, {"cxxnet_tpu/mod.py": src},
             select=["dead-symbol"])
    assert r.findings == [] and len(r.suppressed) == 1


# -- suppression + baseline mechanics -----------------------------------------

def test_suppression_requires_reason(tmp_path):
    src = """\
    import threading

    def go():
        t = threading.Thread(target=go)  # graftlint: disable=thread-shutdown
        t.start()
    """
    r = lint(tmp_path, {"mod.py": src}, select=["thread-shutdown"])
    # the violation is NOT silenced and the bare suppression is itself
    # a finding — reason strings are the whole audit trail
    assert sorted(names(r)) == ["suppression", "thread-shutdown"]
    assert "no reason" in [f for f in r.findings
                           if f.pass_name == "suppression"][0].message


def test_suppression_unknown_pass_is_flagged(tmp_path):
    src = """\
    X = 1  # graftlint: disable=not-a-pass (whatever)
    """
    r = lint(tmp_path, {"mod.py": src}, select=["thread-shutdown"])
    assert names(r) == ["suppression"]
    assert "unknown pass" in r.findings[0].message


def test_selected_run_accepts_foreign_suppressions(tmp_path):
    """--select must not flag valid suppressions of UNSELECTED passes
    (the known-pass set is the full registry, not the selection)."""
    src = """\
    X = 1  # graftlint: disable=config-namespace (fixture literal)
    """
    r = lint(tmp_path, {"mod.py": src}, select=["thread-shutdown"])
    assert r.findings == []


def test_baseline_absorbs_and_survives_line_drift(tmp_path):
    files = {"cxxnet_tpu/elastic/coord.py": _DURABLE_BAD}
    r = lint(tmp_path, files, select=["atomic-io"])
    assert len(r.findings) == 2
    bl_path = str(tmp_path / "graftlint_baseline.json")
    write_baseline(bl_path, r.findings)
    bl = load_baseline(bl_path)

    r2 = lint(tmp_path, files, select=["atomic-io"], baseline=bl)
    assert r2.findings == [] and len(r2.baselined) == 2

    # unrelated edits above the finding must not un-baseline it: the
    # fingerprint hashes the line TEXT, not the line number
    drifted = "import sys  # unrelated new first line\n" + \
        textwrap.dedent(_DURABLE_BAD)
    (tmp_path / "cxxnet_tpu/elastic/coord.py").write_text(drifted)
    proj = Project.load(str(tmp_path), ["cxxnet_tpu"])
    r3 = run_analysis(
        proj, [p for p in default_passes() if p.name == "atomic-io"],
        baseline=bl)
    assert r3.findings == [] and len(r3.baselined) == 2

    # a NEW violation is not covered by the old baseline
    grown = drifted + "\ndef more(path):\n    open(path, 'w')\n"
    (tmp_path / "cxxnet_tpu/elastic/coord.py").write_text(grown)
    proj = Project.load(str(tmp_path), ["cxxnet_tpu"])
    r4 = run_analysis(
        proj, [p for p in default_passes() if p.name == "atomic-io"],
        baseline=bl)
    assert len(r4.findings) == 1 and len(r4.baselined) == 2


def test_baseline_file_format_rejects_garbage(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"version": 999}))
    with pytest.raises(ValueError):
        load_baseline(str(p))


# -- the whole-repo gate ------------------------------------------------------

def _repo_baseline():
    path = os.path.join(REPO, "graftlint_baseline.json")
    return load_baseline(path) if os.path.exists(path) else None


def test_repo_has_zero_unsuppressed_findings():
    """The tier-1 contract: every pass over cxxnet_tpu/, tools/ and
    tests/ comes back clean (fix the code or suppress WITH a reason —
    never silently regress an invariant PRs 3-10 paid review rounds
    to establish)."""
    proj = Project.load(REPO, LINT_PATHS, CONTEXT_PATHS)
    res = run_analysis(proj, default_passes(),
                       baseline=_repo_baseline())
    pretty = "\n".join(f.format() for f in
                       res.parse_errors + res.findings)
    assert res.ok, f"graftlint found unsuppressed violations:\n{pretty}"


def test_repo_suppressions_all_carry_reasons():
    """Every suppression in the tree has a non-empty reason string
    (the parser enforces it per comment; this asserts the global
    inventory so a grep of the codebase matches the policy)."""
    proj = Project.load(REPO, LINT_PATHS, CONTEXT_PATHS)
    for mod in proj.modules:
        for s in mod.suppressions:
            assert s.reason.strip(), \
                f"{mod.rel}:{s.line}: suppression without reason"


def test_cli_contract(tmp_path):
    """tools/graftlint.py: nonzero exit + file:line:col output on a
    violation; --list-passes names every registered pass."""
    bad = tmp_path / "cxxnet_tpu" / "elastic" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent(_DURABLE_BAD))
    cli = os.path.join(REPO, "tools", "graftlint.py")
    r = subprocess.run(
        [sys.executable, cli, "--root", str(tmp_path), "cxxnet_tpu"],
        capture_output=True, text=True)
    assert r.returncode == 1
    assert "cxxnet_tpu/elastic/bad.py:" in r.stdout
    assert "[atomic-io]" in r.stdout

    r2 = subprocess.run([sys.executable, cli, "--list-passes"],
                        capture_output=True, text=True)
    assert r2.returncode == 0
    for name in ("trace-purity", "atomic-io",
                 "signal-safety", "thread-shutdown",
                 "config-namespace", "dead-symbol"):
        assert name in r2.stdout


def test_cli_write_baseline_contract(tmp_path):
    """--write-baseline: the next run really IS clean; findings the
    baseline machinery can never absorb (reason-less suppressions,
    parse errors) fail the write instead of becoming dead entries;
    --select is rejected (a partial run would drop other passes'
    accepted debt)."""
    bad = tmp_path / "cxxnet_tpu" / "elastic" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent(_DURABLE_BAD))
    cli = os.path.join(REPO, "tools", "graftlint.py")
    base = [sys.executable, cli, "--root", str(tmp_path), "cxxnet_tpu"]

    r = subprocess.run(base + ["--write-baseline"],
                       capture_output=True, text=True)
    assert r.returncode == 0
    r2 = subprocess.run(base, capture_output=True, text=True)
    assert r2.returncode == 0, r2.stdout     # accepted debt is silent
    assert "0 finding(s)" in r2.stdout and "baselined" in r2.stdout

    # --select + --write-baseline is a usage error
    r3 = subprocess.run(base + ["--select", "atomic-io",
                                "--write-baseline"],
                        capture_output=True, text=True)
    assert r3.returncode == 2

    # a reason-less suppression cannot be baselined away
    bad.write_text(textwrap.dedent(_DURABLE_BAD).replace(
        "os.rename(path + \".tmp\", path)",
        "os.rename(path + \".tmp\", path)  "
        "# graftlint: disable=atomic-io"))
    r4 = subprocess.run(base + ["--write-baseline"],
                        capture_output=True, text=True)
    assert r4.returncode == 1
    assert "cannot be baselined" in r4.stdout


def test_cli_all_exits_zero():
    """The verify-recipe invocation, exactly as wired: the repo gate
    through the real CLI (subprocess, fresh interpreter, no jax)."""
    cli = os.path.join(REPO, "tools", "graftlint.py")
    r = subprocess.run([sys.executable, cli, "--all"],
                       capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout.splitlines()[-1]
