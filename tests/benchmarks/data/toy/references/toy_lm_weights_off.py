"""Reference module ``toy_lm_weights_off``: ``toy_lm`` with a fault
planted — the reference is handed the initial weights off by 1 %. No
cell of any manifest names it but the ones that have to come out with
``"correct": false``: the tests' (``test_benchmark_references.py``) and
the chip run that showed ``toy_lm``'s check to have teeth at a real size
(PERF.md section 6, PR 27). The count is ``toy_lm``'s own."""

import importlib.util
import os

import jax

_spec = importlib.util.spec_from_file_location(
    "bench_toy_lm", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "toy_lm.py"))
toy_lm = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(toy_lm)

needs_initial_params = toy_lm.needs_initial_params
train_step_flops = toy_lm.train_step_flops


def check(kind, view):
    off = jax.tree_util.tree_map(lambda a: a * 1.01, view["params0"])
    return toy_lm.check(kind, dict(view, params0=off))
