"""shardmap-vjp: custom_vjp x shard_map islands, the PR-9 rule.

The fused mesh ops keep ``custom_vjp`` OUTSIDE their shard_map islands
— fwd and bwd are each their own shard_map (ops/fused_norm.py,
fused_epilogue.py) — so autodiff never transposes an island whose
specs mix sharded and replicated operands: every cross-shard sum such
an op needs is the explicit psum its backward writes, not one the
transpose has to infer (interpreted islands run without shard_map's
varying-axes check, ops/fused.py:island, where an inferred transpose
sums over every unmentioned axis). This pass mechanizes the rule,
including its two sanctioned shapes:

* **all-batch-sharded islands** may wrap a custom_vjp op directly
  (``island(..., in_batch=(True, ...all True), out_batch=True)``):
  with every spec sharded the same way the transpose is collective-
  free and exact (the act-only epilogue / LRN / pool row-local
  pattern);
* an island **inside a custom_vjp-decorated function (or a defvjp-
  registered fwd/bwd)** is fine: the outer custom_vjp intercepts AD,
  so the island is never transposed (the ``_epi_bias_mesh`` pattern).

Everything else — defining a custom_vjp inside an island body, calling
``defvjp`` there, or invoking a custom_vjp-decorated function from a
mixed-spec island with no outer custom_vjp — is flagged.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import (Finding, LintPass, Project, attr_chain,
                   build_parents, call_chain, canonical_chain,
                   import_aliases, last_segment as _last)

_FN = (ast.FunctionDef, ast.AsyncFunctionDef)


def _all_true(node: Optional[ast.AST]) -> bool:
    """Whether an in_batch/out_batch argument is literally all-True
    (bare True or a tuple/list of Trues)."""
    if node is None:
        return False
    if isinstance(node, ast.Constant):
        return node.value is True
    if isinstance(node, (ast.Tuple, ast.List)):
        return bool(node.elts) and all(
            isinstance(e, ast.Constant) and e.value is True
            for e in node.elts)
    return False


class ShardmapVjpPass(LintPass):
    name = "shardmap-vjp"
    description = ("custom_vjp defined or invoked lexically inside a "
                   "shard_map island (a mixed-spec island must not "
                   "be transposed by autodiff)")

    def run(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        for mod in project.modules:
            if mod.tree is None:
                continue
            out.extend(self._run_module(mod))
        return out

    def _run_module(self, mod) -> List[Finding]:
        aliases = import_aliases(mod.tree)

        def canon(node: ast.AST) -> str:
            return canonical_chain(attr_chain(node), aliases)

        parents = build_parents(mod.tree)

        # custom_vjp-decorated function names + names registered as a
        # custom_vjp's fwd/bwd via  X.defvjp(fwd, bwd)
        vjp_names: Set[str] = set()
        ad_exempt_names: Set[str] = set()
        defs_by_name: Dict[str, List[ast.AST]] = {}
        for n in ast.walk(mod.tree):
            if isinstance(n, _FN):
                defs_by_name.setdefault(n.name, []).append(n)
                for dec in n.decorator_list:
                    chains = []
                    if isinstance(dec, ast.Call):
                        chains.append(canon(dec.func))
                        chains.extend(canon(a) for a in dec.args)
                    else:
                        chains.append(canon(dec))
                    if any(_last(c) == "custom_vjp" for c in chains):
                        vjp_names.add(n.name)
            elif isinstance(n, ast.Call) \
                    and _last(call_chain(n)) == "defvjp":
                for a in n.args:
                    if isinstance(a, ast.Name):
                        ad_exempt_names.add(a.id)

        # island bodies: (body fn, wrapping call, exempt?)
        bodies: List[Tuple[ast.AST, bool]] = []
        for n in ast.walk(mod.tree):
            if not isinstance(n, ast.Call):
                continue
            last = _last(canonical_chain(call_chain(n), aliases))
            idx = {"shard_map": 0, "island": 1}.get(last)
            if idx is None or idx >= len(n.args):
                continue
            exempt = False
            if last == "island":
                kw = {k.arg: k.value for k in n.keywords}
                in_b = kw.get("in_batch")
                out_b = kw.get("out_batch")
                if in_b is None and len(n.args) > 2:
                    in_b = n.args[2]
                if out_b is None and len(n.args) > 3:
                    out_b = n.args[3]
                if _all_true(in_b) and _all_true(out_b):
                    # collective-free island: transpose is exact
                    exempt = True
            if not exempt and self._under_custom_vjp(
                    n, parents, vjp_names, ad_exempt_names):
                exempt = True
            arg = n.args[idx]
            targets = []
            if isinstance(arg, ast.Name):
                targets = defs_by_name.get(arg.id, [])
            elif isinstance(arg, (ast.Lambda,) + _FN):
                targets = [arg]
            bodies.extend((t, exempt) for t in targets)

        out: List[Finding] = []
        for body, exempt in bodies:
            bname = getattr(body, "name", "<lambda>")
            for n in ast.walk(body):
                msg = None
                if isinstance(n, (ast.Name, ast.Attribute)) \
                        and _last(attr_chain(n)) == "custom_vjp":
                    # DEFINING a custom_vjp inside an island is never
                    # sanctioned — the exemptions cover invocation only
                    msg = ("custom_vjp defined inside shard_map island "
                           f"'{bname}' — define the vjp OUTSIDE the "
                           "island and wrap only the kernels (PR-9 "
                           "rule: autodiff must not transpose a "
                           "mixed-spec shard_map)")
                elif isinstance(n, ast.Call):
                    if _last(call_chain(n)) == "defvjp":
                        msg = ("defvjp() called inside shard_map "
                               f"island '{bname}' — attach the vjp "
                               "outside the island")
                    elif not exempt and isinstance(n.func, ast.Name) \
                            and n.func.id in vjp_names:
                        msg = (f"custom_vjp function '{n.func.id}' "
                               "invoked inside shard_map island "
                               f"'{bname}' whose specs are not all "
                               "batch-sharded and with no outer "
                               "custom_vjp intercepting AD — hoist "
                               "the custom_vjp above the island")
                if msg:
                    out.append(Finding(
                        self.name, mod.rel, n.lineno, n.col_offset,
                        msg, mod.line_text(n.lineno)))
        return out

    @staticmethod
    def _under_custom_vjp(node: ast.AST, parents: Dict[int, ast.AST],
                          vjp_names: Set[str],
                          ad_exempt: Set[str]) -> bool:
        n = parents.get(id(node))
        while n is not None:
            if isinstance(n, _FN) and (n.name in vjp_names
                                       or n.name in ad_exempt):
                return True
            n = parents.get(id(n))
        return False
