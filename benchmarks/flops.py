"""Operations a train step needs, counted from shapes.

A copy of ``bench.py:analytic_step_flops``'s arithmetic for the layer
kinds of the benchmark's nets, kept here so that a later change to the
program cannot move the yardstick. Only the layers that run on the MXU
count (conv, fullc); elementwise and normalisation work is bandwidth.
A multiply-add is two operations. The backward pass computes two
products per forward product (dX and dW), so a train step is three
times the forward count, less the dX of the layer that reads the
images (bench.py counts that one too: 5 % of AlexNet's step).
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def conv_flops(batch, oy, ox, kh, kw, cin, cout, groups=1) -> float:
    """Forward operations of one convolution: every output element is a
    dot product over its group's ``kh * kw * cin / groups`` inputs."""
    return 2.0 * batch * oy * ox * cout * kh * kw * (cin // groups)


def fullc_flops(batch, n_in, n_out) -> float:
    """Forward operations of one fully connected layer."""
    return 2.0 * batch * n_in * n_out


def layer_flops(record) -> float:
    """Forward operations of one of ``reference.forward``'s per-layer
    records ``(type, name, in_shape NHWC, out_shape NHWC, hyper, reads
    the data node)``; 0 for a layer that does not run on the MXU."""
    kind, _name, in_sh, out_sh, hp = record[:5]
    if kind == "conv":
        k = int(hp.get("kernel_size", 0))
        return conv_flops(
            out_sh[0], out_sh[1], out_sh[2],
            int(hp.get("kernel_height", k)), int(hp.get("kernel_width", k)),
            in_sh[3], out_sh[3], int(hp.get("ngroup", 1)))
    if kind == "fullc":
        return fullc_flops(out_sh[0], in_sh[1] * in_sh[2] * in_sh[3],
                           out_sh[3])
    return 0.0


def forward_flops(records) -> float:
    return sum(layer_flops(r) for r in records)


def train_step_flops(records) -> float:
    """Forward, and a backward of two products per forward product (dX
    and dW) — but one (dW) for a layer that reads the data node: nothing
    asks for the gradient of the images."""
    return sum(layer_flops(r) * (2.0 if r[5] else 3.0) for r in records)


def chip_peaks(device_kind: str) -> dict:
    """``{"bf16_tflops": .., "hbm_gb_s": .., "source": ..}`` for a
    device kind as JAX reports it. A kind that ``peaks.json`` does not
    hold is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no peak FLOP/s and bandwidth on record for device kind "
            f"{device_kind!r}: add it to benchmarks/peaks.json with its "
            "source")
    return table[device_kind]
