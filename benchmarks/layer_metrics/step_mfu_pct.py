"""Share of the chip's bf16 peak that the WHOLE train step reaches:
model-FLOP utilisation. The operations one step of the configuration
needs, counted from the plain reference's layer shapes (``flops.py``:
conv and fullc, two per multiply-add, three times the forward less the
first layer's dX), per second of whole step periods on the device
(``window_s`` runs from the second step's start to the last's on the
``XLA Modules`` line, gaps between steps included: an idle device
lowers it, as it lowers the rate), over the peak (``peaks.json``). On a
mesh each chip does its share of the global batch, and the steps a
second are the mean over the cell's devices, as ``device_idle_pct`` is:
a chip that falls behind lowers it.

The count is the configuration's, not the implementation's: it is the
same whether a BN runs as a Pallas kernel, as XLA's fusion or folded
into a convolution, and whatever ``fused_kernels`` says; elementwise,
normalisation and pooling work is not in it. So a kernel taken off the
path leaves this number standing, and ``step_mfu_pct`` over
``train_items_per_s_chip`` is one constant per configuration:
operations per item over the peak. Never clamped: a reading above 100
means a stale count or a window that is not whole steps, and has to
show."""


def read(view):
    if view["trace"] is None:
        return None
    per_s = [d["steps"] / d["window_s"] for d in view["trace"]["devices"]
             if d["window_s"] > 0 and d["steps"] > 0]
    if not per_s:
        return None                 # no whole step: nothing to read
    achieved = view["step_flops"] / view["chips"] * sum(per_s) / len(per_s)
    return 100.0 * achieved / (view["peaks"]["bf16_tflops"] * 1e12)
