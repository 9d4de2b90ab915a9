"""graftlint: project-invariant static analysis for cxxnet_tpu.

Mechanizes the recurring review-hardening checklist as AST passes
(stdlib-only — runs anywhere the tests run, jax not required). CLI:
``python tools/graftlint.py --all``; gate: ``tests/test_lint.py``.
Docs: doc/tasks.md "Static analysis".
"""

from .core import (Finding, LintPass, LintResult, ModuleInfo, Project,
                   load_baseline, run_analysis, write_baseline)
from .deadcode import DeadSymbolPass
from .durability import AtomicIoPass
from .namespaces import ConfigNamespacePass
from .purity import TracePurityPass
from .signals import SignalSafetyPass
from .threads import ThreadShutdownPass

#: registration order = report order for same-location findings
PASS_CLASSES = (
    TracePurityPass,
    AtomicIoPass,
    SignalSafetyPass,
    ThreadShutdownPass,
    ConfigNamespacePass,
    DeadSymbolPass,
)


def default_passes():
    """Fresh instances of every registered pass (passes are stateless,
    but fresh-per-run keeps that an implementation detail)."""
    return [cls() for cls in PASS_CLASSES]


def pass_names():
    return [cls.name for cls in PASS_CLASSES]
