"""Reference module ``joyai_llm_flash_weights_off``: ``joyai_llm_flash`` with
the fault ``weights_off`` planted (its ``VARIANT``; the module's header says what
each is). No cell of the benchmark names it: only the configurations of
the scratch manifests that have to come out ``"correct": false`` (the
tests' toy size, the builder's chip runs; PERF.md section 6, PR 28).
The operation count is the reference's own."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_joyai_llm_flash_for_weights_off", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), *[".."] * 5,
        "benchmarks", "references", "joyai_llm_flash.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)
ref.VARIANT = "weights_off"

check = ref.check
train_step_flops = ref.train_step_flops
