#!/usr/bin/env python
"""graftlint — project-invariant static analysis for cxxnet_tpu.

Mechanizes the review-hardening checklist (doc/tasks.md "Static
analysis"): trace purity, durable-write atomicity, signal-handler
safety, thread shutdown, config-namespace typos, dead symbols.
Stdlib-only; jax is NOT imported.

Usage:
    python tools/graftlint.py --all              # the tier-1 gate
    python tools/graftlint.py cxxnet_tpu/serve   # one subtree
    python tools/graftlint.py --select atomic-io --all
    python tools/graftlint.py --list-passes
    python tools/graftlint.py --all --write-baseline   # accept debt

Exit status: 0 = clean, 1 = unsuppressed findings (or parse errors),
2 = usage error. Findings print as ``path:line:col: [pass] message``.

Suppressions: ``# graftlint: disable=<pass>[,<pass>] (<reason>)`` on
the flagged line or the line above; ``disable-file=`` for a whole
file. The reason is mandatory. Baseline: ``graftlint_baseline.json``
at the repo root (auto-loaded when present) holds fingerprints of
accepted pre-existing findings.
"""

import argparse
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: lint targets for --all (the tier-1 gate surface)
ALL_LINT = ("cxxnet_tpu", "tools", "tests")
#: reference-only context for --all: feeds dead-symbol reference counts
#: and declared-key tables, but is not itself linted
ALL_CONTEXT = ("bench.py", "__graft_entry__.py", "examples", "wrapper")

BASELINE_NAME = "graftlint_baseline.json"


def _load_analysis():
    """Import cxxnet_tpu.analysis WITHOUT executing cxxnet_tpu's
    package __init__ (which imports jax — a lint over 35k lines must
    not pay a backend init)."""
    pkg_dir = os.path.join(ROOT, "cxxnet_tpu", "analysis")
    name = "cxxnet_tpu.analysis"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    # parent placeholder so the runtime accepts the dotted name without
    # importing the real package __init__
    if "cxxnet_tpu" not in sys.modules:
        parent_spec = importlib.util.spec_from_loader(
            "cxxnet_tpu", loader=None, is_package=True)
        parent = importlib.util.module_from_spec(parent_spec)
        parent.__path__ = [os.path.join(ROOT, "cxxnet_tpu")]
        sys.modules["cxxnet_tpu"] = parent
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help="files/directories to lint (repo-relative)")
    ap.add_argument("--all", action="store_true",
                    help="lint %s (context: %s)" % (
                        " ".join(ALL_LINT), " ".join(ALL_CONTEXT)))
    ap.add_argument("--select", action="append", default=[],
                    metavar="PASS",
                    help="run only these passes (repeat or comma-sep)")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="baseline file (default: %s at the repo root "
                         "when present)" % BASELINE_NAME)
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept current findings into the baseline "
                         "and exit 0")
    ap.add_argument("--root", default=ROOT,
                    help="project root findings/baselines are relative "
                         "to (default: the repo root)")
    ap.add_argument("--list-passes", action="store_true")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="also print suppressed/baselined findings")
    args = ap.parse_args(argv)

    analysis = _load_analysis()

    if args.list_passes:
        for cls in analysis.PASS_CLASSES:
            print("%-18s %s" % (cls.name, cls.description))
        return 0

    paths = list(args.paths)
    context = []
    if args.all:
        paths = [p for p in ALL_LINT
                 if os.path.exists(os.path.join(ROOT, p))] + paths
        context = [p for p in ALL_CONTEXT
                   if os.path.exists(os.path.join(ROOT, p))]
    if not paths:
        ap.error("no paths given (use --all for the full gate)")

    passes = analysis.default_passes()
    if args.select and args.write_baseline:
        # a selected run never executed the other passes, so a baseline
        # regenerated from it would silently DROP their accepted debt
        ap.error("--write-baseline requires a full run "
                 "(drop --select)")
    if args.select:
        want = {n for sel in args.select for n in sel.split(",") if n}
        known = {p.name for p in passes}
        bad = want - known
        if bad:
            ap.error("unknown pass(es): %s (known: %s)" % (
                ", ".join(sorted(bad)), ", ".join(sorted(known))))
        passes = [p for p in passes if p.name in want]

    root = os.path.abspath(args.root)
    baseline_path = args.baseline or os.path.join(root, BASELINE_NAME)
    baseline = None
    if os.path.exists(baseline_path):
        baseline = analysis.load_baseline(baseline_path)

    project = analysis.Project.load(root, paths, context)
    result = analysis.run_analysis(
        project, passes, baseline=baseline,
        known_pass_names=set(analysis.pass_names()))

    if args.write_baseline:
        # suppression-hygiene and parse findings gate unconditionally
        # (run_analysis applies the baseline only to pass findings) —
        # writing their fingerprints would be dead entries that make
        # the next run fail anyway, so surface them instead
        unbaselinable = [f for f in result.findings
                         if f.pass_name in ("suppression", "parse")] \
            + result.parse_errors
        accepted = [f for f in result.findings
                    if f.pass_name not in ("suppression", "parse")]
        analysis.write_baseline(
            baseline_path, accepted + result.baselined)
        print("graftlint: wrote %d fingerprint(s) to %s" % (
            len(accepted) + len(result.baselined),
            os.path.relpath(baseline_path, ROOT)))
        if unbaselinable:
            for f in unbaselinable:
                print(f.format())
            print("graftlint: %d finding(s) above cannot be baselined "
                  "(fix the suppression comments / syntax errors)"
                  % len(unbaselinable))
            return 1
        return 0

    for f in result.parse_errors:
        print(f.format())
    for f in result.findings:
        print(f.format())
    if args.show_suppressed:
        for f in result.suppressed:
            print(f.format() + "  [suppressed]")
        for f in result.baselined:
            print(f.format() + "  [baselined]")

    n_files = len(project.modules)
    print("graftlint: %d finding(s), %d suppressed, %d baselined "
          "across %d files (%d passes)" % (
              len(result.findings) + len(result.parse_errors),
              len(result.suppressed), len(result.baselined),
              n_files, len(passes)))
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
