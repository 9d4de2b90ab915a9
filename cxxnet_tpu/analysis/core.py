"""graftlint core: findings, suppressions, baselines, the project model.

The reference C++ framework got its load-bearing invariants enforced by
the compiler — ``template<typename xpu>`` device polymorphism simply
failed to build when an op touched the wrong device path
(/root/reference/src/global.h). The JAX port's equivalent invariants
(durable writes only through ``write_bytes_atomic``, signal handlers
that only set events, …) are
Python conventions, and PRs 5-10 each shipped a 10+-item review list
fixing fresh violations of exactly these classes. This package turns
that recurring review tax into a mechanized tier-1 gate: stdlib-``ast``
passes over the codebase, run by ``tools/graftlint.py`` and by
``tests/test_lint.py``.

Dependency-free by design (``ast`` + ``tokenize`` only): the lint must
run in any environment the tests run in, including ones without jax.

Vocabulary:

* **Finding** — one violation at ``path:line:col`` from one pass.
* **Suppression** — an inline ``# graftlint: disable=<pass>[,<pass>]
  (<reason>)`` comment. The reason string is REQUIRED — a suppression
  without one is itself reported (pass name ``suppression``). A
  TRAILING comment covers findings on its own physical line only; a
  STANDALONE comment line covers the line directly below it (so it
  can sit above a flagged statement without bleeding further).
  ``disable-file=`` anywhere in a file covers the whole file.
  ``disable=all`` covers every pass.
* **Baseline** — a checked-in JSON set of finding fingerprints that are
  accepted-as-is (pre-existing debt a new pass surfaces in bulk). A
  fingerprint hashes the pass, path, message, and the *text* of the
  flagged line — not the line number — so unrelated edits above a
  baselined finding don't un-baseline it.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import io
import json
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

#: the one suppression grammar (documented in doc/tasks.md "Static
#: analysis"); the word 'disable' after the tool name, then pass
#: names, then the mandatory parenthesized reason
_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*(disable|disable-file)\s*=\s*"
    r"([A-Za-z0-9_,\- ]+?)\s*(?:\((.*)\))?\s*$")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation. ``path`` is repo-relative so output is stable
    across checkouts and fingerprints are shareable."""
    pass_name: str
    path: str
    line: int
    col: int
    message: str
    #: text of the flagged source line (fingerprint input, not output)
    line_text: str = ""

    def format(self) -> str:
        # file:line:col is the clickable convention editors and CI
        # annotators parse
        return (f"{self.path}:{self.line}:{self.col}: "
                f"[{self.pass_name}] {self.message}")

    def fingerprint(self) -> str:
        h = hashlib.sha1()
        h.update(("%s\0%s\0%s\0%s" % (
            self.pass_name, self.path, self.message,
            self.line_text.strip())).encode("utf-8", "replace"))
        return h.hexdigest()[:16]


@dataclasses.dataclass
class _Suppression:
    line: int                 # physical line of the comment
    passes: Tuple[str, ...]   # ("all",) covers everything
    reason: str
    file_wide: bool
    #: standalone comment lines cover the NEXT line; trailing comments
    #: cover only their own
    standalone: bool = False


class ModuleInfo:
    """One parsed source file: AST + line table + suppressions."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[str] = None
        self.suppressions: List[_Suppression] = []
        self.meta_findings: List[Finding] = []
        try:
            self.tree = ast.parse(source, filename=rel)
        except SyntaxError as e:
            self.parse_error = f"syntax error: {e.msg} (line {e.lineno})"
        self._scan_suppressions()

    # -- suppressions ------------------------------------------------------

    def _scan_suppressions(self) -> None:
        try:
            toks = tokenize.generate_tokens(
                io.StringIO(self.source).readline)
            comments = [(t.start[0], t.string) for t in toks
                        if t.type == tokenize.COMMENT]
        except (tokenize.TokenError, IndentationError, SyntaxError):
            comments = [(i + 1, ln[ln.index("#"):])
                        for i, ln in enumerate(self.lines) if "#" in ln]
        for lineno, text in comments:
            if "graftlint" not in text:
                continue
            m = _SUPPRESS_RE.search(text)
            if not m:
                self.meta_findings.append(Finding(
                    "suppression", self.rel, lineno, 0,
                    "malformed graftlint comment; expected "
                    "'# graftlint: disable=<pass> (<reason>)'",
                    self.line_text(lineno)))
                continue
            kind, names, reason = m.group(1), m.group(2), m.group(3)
            passes = tuple(p.strip() for p in names.split(",") if p.strip())
            if not (reason or "").strip():
                # the whole point of the reason requirement: a bare
                # disable is indistinguishable from "shut it up"
                self.meta_findings.append(Finding(
                    "suppression", self.rel, lineno, 0,
                    f"suppression of {'/'.join(passes)} carries no "
                    "reason; write '# graftlint: disable=<pass> "
                    "(<why this is safe>)'", self.line_text(lineno)))
                continue
            src_line = self.line_text(lineno)
            standalone = src_line.lstrip().startswith("#")
            self.suppressions.append(_Suppression(
                line=lineno, passes=passes, reason=reason.strip(),
                file_wide=(kind == "disable-file"),
                standalone=standalone))

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def is_suppressed(self, f: Finding) -> bool:
        for s in self.suppressions:
            if "all" not in s.passes and f.pass_name not in s.passes:
                continue
            covered = (s.line + 1,) if s.standalone else (s.line,)
            if s.file_wide or f.line in covered:
                return True
        return False

    def validate_suppression_passes(self, known: Set[str]) -> List[Finding]:
        out = []
        for s in self.suppressions:
            for p in s.passes:
                if p != "all" and p not in known:
                    out.append(Finding(
                        "suppression", self.rel, s.line, 0,
                        f"suppression names unknown pass {p!r}; known: "
                        + ", ".join(sorted(known)),
                        self.line_text(s.line)))
        return out


class Project:
    """The unit a lint run sees: ``modules`` are linted, while
    ``context_modules`` only feed cross-file indexes (dead-symbol's
    reference counts, config-namespace's declared-key tables) — a
    symbol used only by bench.py is not dead, but bench.py itself is
    not a lint target."""

    def __init__(self, root: str, modules: Sequence[ModuleInfo],
                 context_modules: Sequence[ModuleInfo] = ()):
        self.root = root
        self.modules = list(modules)
        self.context_modules = list(context_modules)

    @property
    def all_modules(self) -> List[ModuleInfo]:
        return self.modules + self.context_modules

    @classmethod
    def load(cls, root: str, paths: Iterable[str],
             context_paths: Iterable[str] = ()) -> "Project":
        root = os.path.abspath(root)

        def _collect(paths: Iterable[str]) -> List[ModuleInfo]:
            files: List[str] = []
            for p in paths:
                # try repo-root-relative first (the gate's spelling),
                # then cwd-relative (ad-hoc CLI invocations)
                ap = p if os.path.isabs(p) else os.path.join(root, p)
                if not os.path.exists(ap):
                    cwd_p = os.path.abspath(p)
                    if os.path.exists(cwd_p):
                        ap = cwd_p
                if os.path.isdir(ap):
                    for dirpath, dirnames, filenames in os.walk(ap):
                        dirnames[:] = [d for d in dirnames
                                       if d != "__pycache__"
                                       and not d.startswith(".")]
                        files.extend(os.path.join(dirpath, fn)
                                     for fn in filenames
                                     if fn.endswith(".py"))
                elif os.path.isfile(ap):
                    files.append(ap)
            out = []
            for fp in sorted(set(files)):
                rel = os.path.relpath(fp, root)
                try:
                    with open(fp, encoding="utf-8") as f:
                        src = f.read()
                except (OSError, UnicodeDecodeError) as e:
                    m = ModuleInfo(fp, rel, "")
                    m.parse_error = f"unreadable: {e}"
                    out.append(m)
                    continue
                out.append(ModuleInfo(fp, rel, src))
            return out

        lint = _collect(paths)
        seen = {m.rel for m in lint}
        ctx = [m for m in _collect(context_paths) if m.rel not in seen]
        return cls(root, lint, ctx)


class LintPass:
    """Base class; subclasses set ``name``/``description`` and
    implement :meth:`run` over the whole project (cross-file passes
    need the full view; per-file passes just loop)."""

    name = ""
    description = ""

    def run(self, project: Project) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError


# -- shared AST helpers --------------------------------------------------------

def attr_chain(node: ast.AST) -> str:
    """Dotted-name string for Name/Attribute chains (``jax.lax.scan``),
    '' for anything not a plain chain (calls, subscripts)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def call_chain(call: ast.Call) -> str:
    return attr_chain(call.func)


def last_segment(chain: str) -> str:
    """Final dotted-name segment: ``jax.lax.scan`` -> ``scan``."""
    return chain.rsplit(".", 1)[-1] if chain else ""


def build_parents(tree: ast.AST) -> Dict[int, ast.AST]:
    """id(child) -> parent map for upward walks (enclosing function /
    class / statement lookups)."""
    out: Dict[int, ast.AST] = {}
    for n in ast.walk(tree):
        for c in ast.iter_child_nodes(n):
            out[id(c)] = n
    return out


def const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def walk_skipping(node: ast.AST,
                  skip: Tuple[type, ...] = ()) -> Iterable[ast.AST]:
    """ast.walk, but do not descend into child nodes of the given
    types (e.g. keep a traced function's scan limited to its own body,
    not nested defs that trace separately or not at all)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, skip):
            stack.extend(ast.iter_child_nodes(n))


def import_aliases(tree: ast.AST) -> Dict[str, str]:
    """name-in-scope -> canonical dotted origin, from module-level (and
    nested — conservative union) imports. ``import numpy as np`` maps
    np -> numpy; ``from time import perf_counter`` maps
    perf_counter -> time.perf_counter."""
    out: Dict[str, str] = {}
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            for a in n.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(n, ast.ImportFrom) and n.module and n.level == 0:
            for a in n.names:
                if a.name == "*":
                    continue
                out[a.asname or a.name] = f"{n.module}.{a.name}"
    return out


def canonical_chain(chain: str, aliases: Dict[str, str]) -> str:
    """Rewrite the chain's root through the module's import aliases:
    ``np.random.normal`` -> ``numpy.random.normal``."""
    if not chain:
        return chain
    head, _, rest = chain.partition(".")
    origin = aliases.get(head)
    if origin is None:
        return chain
    return f"{origin}.{rest}" if rest else origin


# -- baseline ------------------------------------------------------------------

BASELINE_VERSION = 1


def load_baseline(path: str) -> Set[str]:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if not isinstance(data, dict) or data.get("version") != BASELINE_VERSION:
        raise ValueError(
            f"{path}: not a graftlint baseline (want version "
            f"{BASELINE_VERSION})")
    return set(data.get("findings", []))


def write_baseline(path: str, findings: Iterable[Finding]) -> None:
    data = {"version": BASELINE_VERSION,
            "findings": sorted({f.fingerprint() for f in findings})}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")


# -- driver --------------------------------------------------------------------

@dataclasses.dataclass
class LintResult:
    findings: List[Finding]          # unsuppressed, unbaselined: the gate
    suppressed: List[Finding]
    baselined: List[Finding]
    parse_errors: List[Finding]

    @property
    def ok(self) -> bool:
        return not (self.findings or self.parse_errors)


def run_analysis(project: Project, passes: Sequence[LintPass],
                 baseline: Optional[Set[str]] = None,
                 known_pass_names: Optional[Set[str]] = None
                 ) -> LintResult:
    """Run every pass, then apply suppressions and the baseline.
    Suppression-hygiene findings (missing reason, unknown pass) are
    not themselves suppressible — they gate unconditionally.
    ``known_pass_names`` is the FULL registry (so a ``--select`` run
    doesn't flag valid suppressions of unselected passes); defaults to
    the passes actually run."""
    by_rel = {m.rel: m for m in project.modules}
    parse_errors = [
        Finding("parse", m.rel, 1, 0, m.parse_error or "unparseable")
        for m in project.modules if m.parse_error]

    raw: List[Finding] = []
    for p in passes:
        raw.extend(p.run(project))

    kept: List[Finding] = []
    suppressed: List[Finding] = []
    baselined: List[Finding] = []
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.col,
                                        f.pass_name)):
        mod = by_rel.get(f.path)
        if mod is not None and mod.is_suppressed(f):
            suppressed.append(f)
        elif baseline and f.fingerprint() in baseline:
            baselined.append(f)
        else:
            kept.append(f)

    known = set(known_pass_names or (p.name for p in passes)) \
        | {"parse", "suppression"}
    for m in project.modules:
        kept.extend(m.meta_findings)
        kept.extend(m.validate_suppression_passes(known))
    kept.sort(key=lambda f: (f.path, f.line, f.col, f.pass_name))
    return LintResult(findings=kept, suppressed=suppressed,
                      baselined=baselined, parse_errors=parse_errors)
