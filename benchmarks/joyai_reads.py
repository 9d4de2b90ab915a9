"""What the layer metrics of the ``joyai_llm_flash`` configuration share.

Its layer kinds trace their parts under sub-scopes of the layer's own
scope (``moe.route``, ``moe.experts``, ``moe.combine``, ``moe.shared``,
``mla.proj``, ``mla.attend``, ``head_loss``), and the trainer adds the
sigmoid-routed layers' pair counts to the telemetry registry as it
drains the train metric (``cxxnet_moe_*``). Every reader returns
``None`` where there is nothing to read — no device trace, a program
without the scope or the counter, as every program before PR 28 is —
and the metric is then left out of the line.

The operations a roofline share is over are the configuration's own,
counted by its reference module (``references/joyai_llm_flash.py``)
from the keys of its file: nothing here knows a width.
"""

import importlib.util
import json
import os

from benchmarks.program_reads import _program

_HERE = os.path.dirname(os.path.abspath(__file__))


def subscope_seconds(view, scope):
    """``(device seconds of the first device's instructions traced under
    ``scope``, steps)``, forward, rebuilt forward and backward alike; or
    ``None``."""
    if view.get("trace") is None:
        return None
    got = _program()
    if got is None:
        return None
    try:
        from cxxnet_tpu.telemetry.traceparse import scope_path
    except ImportError:
        return None
    table = got[0]
    dev = view["trace"]["devices"][0]
    hit, found = 0.0, False
    for key, seconds in dev["by_name"].items():
        name = key.rsplit(" ", 1)[-1].lstrip("%")
        op_name = table.get(name)
        if op_name and scope in scope_path(op_name)[1]:
            hit, found = hit + seconds, True
    return (hit, dev["steps"]) if found and dev["steps"] else None


def subscope_ms_per_step(view, scope):
    got = subscope_seconds(view, scope)
    return None if got is None else 1e3 * got[0] / got[1]


def _family(name):
    try:
        from cxxnet_tpu.telemetry.registry import REGISTRY
    except ImportError:
        return None
    return REGISTRY.get(name)


def counter(name):
    """The registry's unlabelled counter or gauge ``name``, or ``None``."""
    fam = _family(name)
    return None if fam is None else fam.value


def gauge_max(name):
    """The largest child of the registry's labelled gauge, or ``None``."""
    fam = _family(name)
    values = [child.value for _, child in fam.samples()] if fam else []
    return max(values) if values else None


def configuration():
    """``(the configuration file's keys, its reference module)``."""
    with open(os.path.join(_HERE, "configs", "joyai_llm_flash.json")) as f:
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "bench_joyai_llm_flash_counts",
        os.path.join(_HERE, "references", "joyai_llm_flash.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return config, ref


def roofline_pct(view, scope, flops_per_step):
    """``flops_per_step`` over the device time a step spends under
    ``scope``, over the chip's bf16 peak (compute-bound products: the
    bound is the operations')."""
    got = subscope_seconds(view, scope)
    if got is None or got[0] <= 0 or flops_per_step is None:
        return None
    seconds, steps = got
    return 100.0 * flops_per_step * steps / seconds \
        / (view["peaks"]["bf16_tflops"] * 1e12)
