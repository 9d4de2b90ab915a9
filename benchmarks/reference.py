"""Plain float32 reference of the benchmark's convnets.

A straightforward ``jax.numpy`` / ``lax`` forward pass and softmax loss,
independent of the program under test: no Pallas kernel, no layer class,
no graph rewrite, no mixed precision. From the program it takes only
what a checkpoint reader would: the parsed layer list (``LayerSpec``:
type, name, input and output node indices, ``key = value`` pairs) and
the weights by layer name, in the program's layouts — activations NHWC,
conv filters HWIO ``(kh, kw, cin/groups, cout)``, fullc ``(in, out)``,
flatten in ``(y, x, c)`` order.

Layer kinds: conv (groups), batch_norm (training mode over the whole
batch it is given, or the running statistics in eval mode), relu,
max/avg pooling (cxxnet's ceil-mode geometry; avg divides by k*k,
padded cells included), lrn, split, ch_concat, flatten, fullc, dropout
(off), softmax. Any other kind is an error: a configuration that needs
one brings its own reference.

Every matrix product runs under ``jax.default_matmul_precision
("highest")``: on a TPU a float32 product is otherwise computed in
bfloat16 passes, and the reference would share the error it is there
to expose.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _hyper(spec, defaults):
    """The layer's ``key = value`` pairs over the net's global ones
    (cxxnet hands every layer the globals first, then its own)."""
    hp = dict(defaults)
    hp.update(dict(spec.cfg))
    return hp


def _window(hp):
    """(kh, kw, stride, pad_y, pad_x) of a conv or pooling layer."""
    k = int(hp.get("kernel_size", 0))
    kh = int(hp.get("kernel_height", k))
    kw = int(hp.get("kernel_width", k))
    p = int(hp.get("pad", 0))
    return (kh, kw, int(hp.get("stride", 1)),
            int(hp.get("pad_y", p)), int(hp.get("pad_x", p)))


def pool_out(size: int, k: int, s: int, p: int) -> int:
    """cxxnet's ceil-mode pooled size (pooling_layer-inl.hpp:111-120)."""
    return min(size + 2 * p - k + s - 1, size + 2 * p - 1) // s + 1


def _pool(x, hp, kind):
    kh, kw, s, py, px = _window(hp)
    _, y, xx, _ = x.shape
    oy, ox = pool_out(y, kh, s, py), pool_out(xx, kw, s, px)
    # trailing pad so a VALID window pass yields the ceil-mode size
    ey = max(0, (oy - 1) * s + kh - (y + 2 * py))
    ex = max(0, (ox - 1) * s + kw - (xx + 2 * px))
    pad = ((0, 0), (py, py + ey), (px, px + ex), (0, 0))
    dims, strides = (1, kh, kw, 1), (1, s, s, 1)
    if kind == "max_pooling":
        return lax.reduce_window(x, -np.inf, lax.max, dims, strides, pad)
    total = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
    return total / float(kh * kw)


def _lrn(x, hp):
    n = int(hp.get("local_size", 3))
    alpha = float(hp.get("alpha", 0.001))
    beta = float(hp.get("beta", 0.75))
    knorm = float(hp.get("knorm", 1.0))
    half = n // 2
    sq = jnp.pad(x * x, ((0, 0), (0, 0), (0, 0), (half, n - 1 - half)))
    c = x.shape[-1]
    win = sum(sq[..., i:i + c] for i in range(n))
    return x * jnp.power(knorm + (alpha / n) * win, -beta)


def _batch_norm(x, p, state, hp, train):
    eps = float(hp.get("eps", 1e-10))
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    else:
        mean, var = state["running_exp"], state["running_var"]
    return (x - mean) * lax.rsqrt(var + eps) * p["wmat"] + p["bias"]


def forward(layers, defaults, params, net_state, data, train, record=None):
    """Run the net on ``data`` (NHWC, already normalised). Returns the
    value of every node by index; the last layer's output node holds the
    softmax probabilities. ``record``, when a list, receives one
    ``(type, name, in_shape, out_shape, hyper, reads the data node)``
    per layer — what ``flops.py`` counts from."""
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), t)
    nodes = {0: jnp.asarray(data, jnp.float32)}
    with jax.default_matmul_precision("highest"):
        for spec in layers:
            hp = _hyper(spec, defaults)
            ins = [nodes[i] for i in spec.nindex_in]
            x = ins[0]
            p = f32(params.get(spec.name, {}))
            kind = spec.type
            if kind == "conv":
                kh, kw, s, py, px = _window(hp)
                y = lax.conv_general_dilated(
                    x, p["wmat"], (s, s), ((py, py), (px, px)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    feature_group_count=int(hp.get("ngroup", 1)),
                    precision=lax.Precision.HIGHEST)
                outs = [y + p["bias"] if "bias" in p else y]
            elif kind == "fullc":
                y = jnp.dot(x.reshape(x.shape[0], -1), p["wmat"],
                            precision=lax.Precision.HIGHEST)
                y = y + p["bias"] if "bias" in p else y
                outs = [y.reshape(y.shape[0], 1, 1, -1)]
            elif kind == "batch_norm":
                outs = [_batch_norm(x, p, f32(net_state.get(spec.name, {})),
                                    hp, train)]
            elif kind == "relu":
                outs = [jnp.maximum(x, 0.0)]
            elif kind in ("max_pooling", "avg_pooling"):
                outs = [_pool(x, hp, kind)]
            elif kind == "lrn":
                outs = [_lrn(x, hp)]
            elif kind == "split":
                outs = [x] * len(spec.nindex_out)
            elif kind == "ch_concat":
                outs = [jnp.concatenate(ins, axis=-1)]
            elif kind == "flatten":
                outs = [x.reshape(x.shape[0], 1, 1, -1)]
            elif kind == "dropout":
                outs = [x]          # off: the mask is the program's own
            elif kind == "softmax":
                z = x.reshape(x.shape[0], -1)
                outs = [jax.nn.softmax(z, axis=-1).reshape(x.shape)]
            else:
                raise ValueError(
                    f"reference.py has no layer kind {kind!r} "
                    f"(layer {spec.name!r})")
            if record is not None:
                record.append((kind, spec.name, tuple(x.shape),
                               tuple(outs[0].shape), hp,
                               0 in spec.nindex_in))
            for i, v in zip(spec.nindex_out, outs):
                nodes[i] = v
    return nodes


def normalise(data, norm):
    """A uint8 batch with deferred normalisation (``device_normalize``
    pipelines) as the float32 tensor the net sees:
    ``(x - mean) * scale / divideby``."""
    x = jnp.asarray(data, jnp.float32)
    if norm is None:
        return x
    if norm.get("mean") is not None:
        x = x - jnp.asarray(np.asarray(norm["mean"], np.float32))
    return x * np.float32(float(norm.get("scale", 1.0))
                          / float(norm.get("divideby", 1.0)))


def make_loss_fn(layers, defaults):
    """jit-able ``(params, data, label) -> mean softmax cross-entropy``
    with batch norm in training mode over the whole batch given (the
    global batch, when ``data`` is sharded over a mesh: GSPMD partitions
    this same plain function)."""
    top = layers[-1].nindex_out[0]

    def loss(params, data, label):
        probs = forward(layers, defaults, params, {}, data, True)[top]
        probs = probs.reshape(probs.shape[0], -1)
        idx = jnp.asarray(label)[:, 0].astype(jnp.int32)
        picked = jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]
        return -jnp.mean(jnp.log(jnp.maximum(picked, 1e-30)))
    return loss


def make_eval_fn(layers, defaults):
    """jit-able ``(params, net_state, data) -> softmax rows`` in eval
    mode (dropout off, batch norm on its running statistics)."""
    top = layers[-1].nindex_out[0]

    def probs(params, net_state, data):
        out = forward(layers, defaults, params, net_state, data, False)[top]
        return out.reshape(out.shape[0], -1)
    return probs


def centered_log(probs):
    """Softmax rows as logits up to the row constant softmax forgets:
    ``log p - mean(log p)``. Random weights give near-uniform rows, where
    comparing probabilities would pass anything; these differ as the
    logits do."""
    lp = np.log(np.maximum(np.asarray(probs, np.float64), 1e-30))
    return lp - lp.mean(axis=1, keepdims=True)
