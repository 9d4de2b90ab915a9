"""What the layer metrics of the ``keye_vl_2_0_30b_a3b`` configuration
share.

Its attention kind (``dsa``: ``gqa`` with its indexer on) traces its parts
under sub-scopes of the layer's own scope — ``gqa.proj``, ``gqa.index``
(the indexer's projections and scores), ``gqa.select`` (the exact top-k),
``gqa.attend.sparse`` (the selection kernels, forward and backward, and
the head-summed distribution the indexer learns from), ``gqa.index_loss``
— and its expert layers are the no-drop ``moe`` under the softmax score
function: the same ``moe.*`` sub-scopes and ``cxxnet_moe_*`` counters as
the other two sequence configurations', read through
``benchmarks/joyai_reads.py``'s functions. Every reader returns ``None``
where there is nothing to read — no device trace, a program without the
scope or the counter, as every program before PR 34 is — and the metric
is then left out of the line.

The operations a roofline share is over are the configuration's own,
counted by its reference module (``references/keye_vl_2_0_30b_a3b.py``)
from the keys of its file: nothing here knows a width.
"""

import importlib.util
import json
import os

from benchmarks.joyai_reads import roofline_pct

_HERE = os.path.dirname(os.path.abspath(__file__))


def configuration():
    """``(the configuration file's keys, its reference module)``."""
    with open(os.path.join(_HERE, "configs",
                           "keye_vl_2_0_30b_a3b.json")) as f:
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "bench_keye_vl_2_0_30b_a3b_counts",
        os.path.join(_HERE, "references", "keye_vl_2_0_30b_a3b.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return config, ref


def products_roofline_pct(view, scope, count):
    """Share of the chip's bf16 peak that the products ``count`` names
    (a function of the reference module: ``(config, positions) ->``
    operations of all layers on one row, forward) reach, forward once
    and backward twice, over the device time under ``scope``."""
    config, ref = configuration()
    positions = int(config["input_shape"][-1])
    return roofline_pct(
        view, scope, 3.0 * view["rows"] / view["chips"]
        * getattr(ref, count)(config, positions))
