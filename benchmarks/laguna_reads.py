"""What the layer metrics of the ``laguna_s_2_1`` configuration share.

Its attention kind traces its parts under sub-scopes of the layer's own
scope (``gqa.proj``, ``gqa.attend.full`` / ``gqa.attend.window``,
``gqa.gate``), and its expert layers are the no-drop ``moe`` under
another score function: the same ``moe.*`` sub-scopes and the same
``cxxnet_moe_*`` counters as ``joyai_llm_flash``'s, read through
``benchmarks/joyai_reads.py``'s functions. Every reader returns ``None``
where there is nothing to read — no device trace, a program without the
scope or the counter, as every program before PR 32 is — and the metric
is then left out of the line.

The operations a roofline share is over are the configuration's own,
counted by its reference module (``references/laguna_s_2_1.py``) from the
keys of its file: nothing here knows a width.
"""

import importlib.util
import json
import os

from benchmarks.joyai_reads import roofline_pct

_HERE = os.path.dirname(os.path.abspath(__file__))


def configuration():
    """``(the configuration file's keys, its reference module)``."""
    with open(os.path.join(_HERE, "configs", "laguna_s_2_1.json")) as f:
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "bench_laguna_s_2_1_counts",
        os.path.join(_HERE, "references", "laguna_s_2_1.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return config, ref


def attend_roofline_pct(view, kind):
    """Share of the chip's bf16 peak that the attention products of the
    layers of type ``kind`` reach: q.k and p.v over the pairs each head
    attends (the causal triangle, or the band), forward once and
    backward twice, over the device time under the kind's scope."""
    config, ref = configuration()
    positions = int(config["input_shape"][-1])
    scope = {"full_attention": "gqa.attend.full",
             "sliding_attention": "gqa.attend.window"}[kind]
    return roofline_pct(
        view, scope, 3.0 * view["rows"] / view["chips"]
        * ref.attention_flops(config, positions, kind))
