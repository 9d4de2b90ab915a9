"""trace-purity: host-side impurities inside code that jax traces.

``time.*`` / stdlib ``random.*`` / ``np.random.*`` calls, ``print``,
``.item()`` / ``float()``-on-array, ``np.asarray`` and
``block_until_ready`` inside a traced function are either (a) baked
into the compiled graph as constants measured once at trace time
(clocks, RNG draws — the classic "why is my timestamp frozen" bug), or
(b) forced host syncs that stall the device pipeline (the PR-4/6
timed-loop rule: one hidden ``.item()`` in a step body flattens the
async dispatch window the whole steptime probe exists to measure).

What counts as traced, per module (lexical — no cross-module closure,
which keeps the pass precise instead of drowning callers in maybes):

* functions decorated with ``jax.jit`` / ``jax.pmap`` /
  ``jax.custom_vjp`` (bare or via ``functools.partial``),
* functions passed to ``jax.jit`` / ``pmap`` / ``vmap`` / ``grad`` /
  ``value_and_grad`` / ``lax.scan`` / ``lax.fori_loop`` /
  ``lax.while_loop`` / ``lax.cond`` / ``lax.switch`` /
  ``shard_map`` / ``pl.pallas_call`` /
  ``*.defvjp``,
* any same-module function called by name from a traced body
  (transitive closure), including lambdas.

Trace-time-only helpers (backend queries, shape math, one-time
warnings) live OUTSIDE traced functions in this codebase's idiom —
anything this pass flags is lexically inside a traced body.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from .core import (Finding, LintPass, ModuleInfo, Project, attr_chain,
                   call_chain, canonical_chain, import_aliases,
                   last_segment as _last, walk_skipping)

_FN = (ast.FunctionDef, ast.AsyncFunctionDef)
_FN_OR_LAMBDA = _FN + (ast.Lambda,)

#: decorator chains (canonicalized, by last segment) that make the
#: decorated function a traced root
_TRACING_DECOS = {"jit", "pmap", "custom_vjp"}

#: call last-segment -> indexes of the arguments that are traced
#: callables (None = all positional args)
_ENTRY_ARGS: Dict[str, Tuple[int, ...]] = {
    "jit": (0,), "pmap": (0,), "vmap": (0,), "grad": (0,),
    "value_and_grad": (0,), "scan": (0,), "shard_map": (0,),
    "pallas_call": (0,), "fori_loop": (2,),
    "while_loop": (0, 1), "cond": (1, 2), "custom_vjp": (0,),
    "checkpoint": (0,), "remat": (0,),
}


class _ModuleView:
    """Function index + traced-set closure for one module."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.aliases = import_aliases(mod.tree)
        # simple name -> every def with that name (module-level and
        # nested; collisions mark all — conservative)
        self.defs_by_name: Dict[str, List[ast.AST]] = {}
        for n in ast.walk(mod.tree):
            if isinstance(n, _FN):
                self.defs_by_name.setdefault(n.name, []).append(n)
        # id(node) -> (node, why-traced)
        self.traced: Dict[int, Tuple[ast.AST, str]] = {}

    def canon(self, node: ast.AST) -> str:
        return canonical_chain(attr_chain(node), self.aliases)

    def _mark(self, target: ast.AST, why: str) -> None:
        if isinstance(target, ast.Name):
            for d in self.defs_by_name.get(target.id, []):
                if id(d) not in self.traced:
                    self.traced[id(d)] = (d, why)
        elif isinstance(target, _FN_OR_LAMBDA):
            if id(target) not in self.traced:
                self.traced[id(target)] = (target, why)

    def find_roots(self) -> None:
        for n in ast.walk(self.mod.tree):
            if isinstance(n, _FN):
                for dec in n.decorator_list:
                    for chain in self._deco_chains(dec):
                        if _last(chain) in _TRACING_DECOS:
                            self._mark(n, chain)
            elif isinstance(n, ast.Call):
                chain = canonical_chain(call_chain(n), self.aliases)
                last = _last(chain)
                if last == "defvjp":
                    for a in n.args:
                        self._mark(a, chain)
                    continue
                idxs = _ENTRY_ARGS.get(last)
                if idxs is None:
                    continue
                # 'scan' etc. are common method names; require a jax-ish
                # chain for the ambiguous ones (bare names were already
                # canonicalized through from-imports)
                if last in ("scan", "fori_loop", "while_loop", "cond",
                            "checkpoint", "remat") \
                        and not ("lax" in chain
                                 or chain.startswith("jax.")):
                    continue
                for i in idxs:
                    if i < len(n.args):
                        self._mark(n.args[i], chain)

    def _deco_chains(self, dec: ast.AST) -> List[str]:
        """A decorator's relevant chains: the decorator itself, and —
        for ``partial(...)`` decorators — every argument chain."""
        out = []
        if isinstance(dec, ast.Call):
            fc = canonical_chain(call_chain(dec), self.aliases)
            out.append(fc)
            if _last(fc) == "partial":
                out.extend(canonical_chain(attr_chain(a), self.aliases)
                           for a in dec.args)
        else:
            out.append(canonical_chain(attr_chain(dec), self.aliases))
        return [c for c in out if c]

    def body_region(self, fn: ast.AST):
        """Nodes of a traced function's own body, not descending into
        nested defs/lambdas (those trace — or don't — on their own)."""
        body = fn.body if isinstance(fn, _FN) else [fn.body]
        for stmt in body:
            yield stmt
            if not isinstance(stmt, _FN_OR_LAMBDA):
                yield from walk_skipping(stmt, skip=_FN_OR_LAMBDA)

    def close_over_calls(self) -> None:
        """Same-module closure: a function called by name from a traced
        body is traced too."""
        changed = True
        while changed:
            changed = False
            for _, (fn, why) in list(self.traced.items()):
                name = getattr(fn, "name", "<lambda>")
                for n in self.body_region(fn):
                    if isinstance(n, ast.Call) \
                            and isinstance(n.func, ast.Name):
                        for d in self.defs_by_name.get(n.func.id, []):
                            if id(d) not in self.traced:
                                self.traced[id(d)] = (
                                    d, f"called from traced '{name}'")
                                changed = True


class TracePurityPass(LintPass):
    name = "trace-purity"
    description = ("host-side impurities (time/random/print/.item()/"
                   "np.asarray/host syncs) inside jax-traced functions")

    def run(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        for mod in project.modules:
            if mod.tree is None:
                continue
            view = _ModuleView(mod)
            view.find_roots()
            view.close_over_calls()
            for _, (fn, why) in sorted(view.traced.items()):
                name = getattr(fn, "name", "<lambda>")
                for n in view.body_region(fn):
                    msg = self._impurity(n, view)
                    if msg:
                        out.append(Finding(
                            self.name, mod.rel, n.lineno, n.col_offset,
                            f"{msg} inside traced function '{name}' "
                            f"(traced via {why})",
                            mod.line_text(n.lineno)))
        return out

    def _impurity(self, n: ast.AST, view: _ModuleView) -> Optional[str]:
        if not isinstance(n, ast.Call):
            return None
        if isinstance(n.func, ast.Attribute):
            if n.func.attr == "item" and not n.args:
                return ".item() forces a device->host sync"
            if n.func.attr == "block_until_ready":
                return "block_until_ready() forces a host sync"
        chain = view.canon(n.func)
        if chain.startswith("time."):
            return (f"wall-clock call {chain}() is frozen at trace "
                    "time (measure outside the traced body)")
        if chain.startswith("random."):
            return (f"stdlib {chain}() draws once at trace time "
                    "(use jax.random with a threaded key)")
        if chain.startswith("numpy.random."):
            return (f"{chain}() draws once at trace time "
                    "(use jax.random with a threaded key)")
        if chain in ("numpy.asarray", "numpy.array"):
            return (f"{chain}() materializes the array on the host "
                    "(use jnp inside traced code)")
        if chain == "jax.device_get":
            return "jax.device_get() forces a device->host transfer"
        if chain == "print":
            return "print() runs once at trace time (use jax.debug.print)"
        # float()/int() on a bare name is overwhelmingly a static
        # python hyperparameter (float(wd) feeding a kernel kwarg);
        # flag only the array-shaped argument forms — subscripts
        # (float(losses[0])) and calls (float(x.mean()))
        if chain in ("float", "int") and n.args and isinstance(
                n.args[0], (ast.Subscript, ast.Call)):
            return (f"{chain}() on a computed value forces a "
                    "device->host sync")
        return None
