"""Share of the chip's bf16 peak that the ops holding a convolution or
a dot reach: operations of the conv and fullc layers of one step, from
shapes (``flops.py``), over the summed device time of those ops (the
trace's own category), over the peak (``peaks.json``). First device;
on a mesh each chip does its share of the global batch."""


def read(view):
    if view["trace"] is None:
        return None
    dev = view["trace"]["devices"][0]
    if dev["mxu_s"] <= 0:
        return None
    achieved = view["step_flops"] / view["chips"] * dev["steps"] \
        / dev["mxu_s"]
    return 100.0 * achieved / (view["peaks"]["bf16_tflops"] * 1e12)
