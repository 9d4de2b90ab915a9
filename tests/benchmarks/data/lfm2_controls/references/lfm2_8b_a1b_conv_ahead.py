"""Reference module ``lfm2_8b_a1b_conv_ahead``: ``lfm2_8b_a1b`` with the
fault ``conv_ahead`` planted (its ``VARIANT``: the convolution's taps
moved one position on, so that t reads t+1; the module's header says
what each is). No cell of the benchmark names it: only the
configurations of the scratch manifests that have to come out
``"correct": false`` (the tests' toy size, and the readings on the chip
in PERF.md section 6). The operation count is the reference's own."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_lfm2_8b_a1b_for_conv_ahead", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), *[".."] * 5,
        "benchmarks", "references", "lfm2_8b_a1b.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)
ref.VARIANT = "conv_ahead"

check = ref.check
train_step_flops = ref.train_step_flops
