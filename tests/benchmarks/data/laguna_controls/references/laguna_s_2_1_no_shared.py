"""Reference module ``laguna_s_2_1_no_shared``: ``laguna_s_2_1`` with
the fault ``no_shared`` planted (its ``VARIANT``; the module's header says what
each is). No cell of the benchmark names it: only the configurations of
the scratch manifests that have to come out ``"correct": false`` (the
tests' toy size, the builder's chip runs; PERF.md section 6, PR 32).
The operation count is the reference's own."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_laguna_s_2_1_for_no_shared", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), *[".."] * 5,
        "benchmarks", "references", "laguna_s_2_1.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)
ref.VARIANT = "no_shared"

check = ref.check
train_step_flops = ref.train_step_flops
