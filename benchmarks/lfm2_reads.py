"""What the layer metrics of the ``lfm2_8b_a1b`` configuration share.

Its short convolution (the kind ``shortconv``) traces its parts under two
sub-scopes of the layer's own scope: ``shortconv.proj`` (the in- and
out-projections, on the MXU) and ``shortconv.mix`` (the gates and the
causal depthwise convolution), read through ``benchmarks/joyai_reads.py``'s
scope functions, forward, rebuilt forward (``remat = 1``) and backward
alike. Every reader returns ``None`` where there is nothing to read — no
device trace, a program without the kind's scopes — and the metric is then left out of the line.

The operations a roofline share is over are the configuration's own,
counted by its reference module (``references/lfm2_8b_a1b.py``) from the
keys of its file: nothing here knows a width.
"""

import importlib.util
import json
import os

from benchmarks import joyai_reads

_HERE = os.path.dirname(os.path.abspath(__file__))

#: the kind's scopes: an instruction of a ``shortconv`` layer is under one
SCOPES = ("shortconv.proj", "shortconv.mix")


def kind_seconds(view):
    """``(device seconds of the first device's instructions under either
    of ``SCOPES``, steps)``; or ``None``."""
    got = [g for g in (joyai_reads.subscope_seconds(view, scope)
                       for scope in SCOPES) if g is not None]
    return (sum(g[0] for g in got), got[0][1]) if got else None


def kind_ms_per_step(view):
    got = kind_seconds(view)
    return None if got is None else 1e3 * got[0] / got[1]


def configuration():
    """``(the configuration file's keys, its reference module)``."""
    with open(os.path.join(_HERE, "configs", "lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "bench_lfm2_8b_a1b_counts",
        os.path.join(_HERE, "references", "lfm2_8b_a1b.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return config, ref


def shortconv_roofline_pct(view):
    """The short convolutions' model operations (the reference module's
    ``shortconv_flops``), forward once and backward twice, over the
    device time under every ``shortconv`` scope, over the chip's bf16
    peak. Never clamped."""
    got = kind_seconds(view)
    if got is None or got[0] <= 0:
        return None
    config, ref = configuration()
    positions = int(config["input_shape"][-1])
    flops = 3.0 * view["rows"] / view["chips"] \
        * ref.shortconv_flops(config, positions)
    seconds, steps = got
    return 100.0 * flops * steps / seconds \
        / (view["peaks"]["bf16_tflops"] * 1e12)
