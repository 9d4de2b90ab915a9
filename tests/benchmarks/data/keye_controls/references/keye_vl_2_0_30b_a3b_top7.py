"""Reference module ``keye_vl_2_0_30b_a3b_top7``: ``keye_vl_2_0_30b_a3b``
with the fault ``top7`` planted (its ``VARIANT``; the module's header says
what each is). No cell of the benchmark names it: only the configurations
of the scratch manifests that have to come out ``"correct": false`` (the
tests' toy size, the builder's chip runs; PERF.md section 6, PR 34). The
operation count is the reference's own."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_keye_vl_2_0_30b_a3b_for_top7", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), *[".."] * 5,
        "benchmarks", "references", "keye_vl_2_0_30b_a3b.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)
ref.VARIANT = "top7"

check = ref.check
train_step_flops = ref.train_step_flops
