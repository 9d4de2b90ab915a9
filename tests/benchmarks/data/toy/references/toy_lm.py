"""Reference module ``toy_lm``: the plain float32 reference, ``correct``
and operation count of a small causal transformer, as a configuration
brings them to ``benchmarks/run.py`` (its header has the contract).

A straightforward ``jax.numpy`` forward pass and mean token
cross-entropy, independent of the program under test: no layer class,
no attention kernel, no mixed precision. From the program it takes only
what a checkpoint reader would: the parsed layer list (type, name,
input and output node indices, ``key = value`` pairs) and the weights
by layer name, in the program's layouts — a node ``(rows, S, E)``; embed
``wmat (V, E)``; layernorm ``gamma``, ``beta``; mha ``q``/``k``/``v``
``wmat (E, heads, d)``, ``bias (heads, d)`` and ``o`` ``wmat (heads, d,
E)``, ``bias (E)``; ffn ``h`` / ``o`` and seqfc ``wmat (in, out)``,
``bias``.

Layer kinds: embed, layernorm, mha (softmax(q k' / sqrt(d)) v, causal or
not, no rotary embedding), ffn (gelu, tanh form, or relu), add, seqfc,
lmloss. Any other kind is an error: a configuration that needs one
brings its own reference.

Every product runs under ``jax.default_matmul_precision("highest")``:
on a TPU a float32 product is otherwise computed in bfloat16 passes, and
the reference would share the error it is there to expose.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: |program's first-step loss - reference's|, by the program's compute
#: dtype; each limit stands between the largest reading of sound runs
#: and the smallest of the planted fault (``toy_lm_weights_off.py``: the
#: reference handed weights off by 1 %), nearer the sound side.
#: float32 (the CPU rehearsal, 8 rows of 64 positions, a loss of ln 32 =
#: 3.47): both sides compute in float32 in different orders and read 0
#: ... 7.2e-7 on ten seeds, three roundings of the loss at most; the
#: fault reads 2.6e-5 ... 2.3e-4 on the same seeds (sandbox CPU, PR 27).
#: bfloat16 (``chip_lm`` on a v5e, 8 rows of 2048 positions, a loss of
#: 9.06): the program reads 4.8e-6 ... 3.5e-5 on twelve seeds — the
#: roundings of 16 384 positions average out — and the fault 1.96e-3 ...
#: 2.18e-3 on the same seeds (my chip runs, PR 27;
#: ``tests/benchmarks/data/chip_lm/readings.py``).
LOSS_TOL = {"float32": 5e-6, "bfloat16": 2e-4}


def _hyper(spec, defaults):
    hp = dict(defaults)
    hp.update(dict(spec.cfg))
    return hp


def _linear(x, p):
    y = jnp.einsum("...i,io->...o", x, p["wmat"])
    return y + p["bias"] if "bias" in p else y


def _layernorm(x, p, hp):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + float(hp.get("eps", 1e-5)))
    return y * p["gamma"] + p["beta"]


def _mha(x, p, hp):
    if int(hp.get("rope", 0)):
        raise ValueError("toy_lm.py has no rotary embedding")

    def heads(name):
        y = jnp.einsum("bse,ehd->bhsd", x, p[name]["wmat"])
        if "bias" in p[name]:
            y = y + p[name]["bias"][None, :, None, :]
        return y
    q, k, v = heads("q"), heads("k"), heads("v")
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if int(hp.get("causal", 0)):
        s = scores.shape[-1]
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    y = jnp.einsum("bhsd,hde->bse", out, p["o"]["wmat"])
    return y + p["o"]["bias"] if "bias" in p["o"] else y


def _ffn(x, p, hp):
    h = _linear(x, p["h"])
    h = jnp.maximum(h, 0.0) if hp.get("act", "gelu") == "relu" \
        else jax.nn.gelu(h, approximate=True)
    return _linear(h, p["o"])


def forward(layers, defaults, params, tokens):
    """Log-probabilities ``(rows, S, V)`` of the net on ``tokens``
    ``(rows, S)`` integer ids."""
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), t)
    nodes = {0: tokens}
    with jax.default_matmul_precision("highest"):
        for spec in layers:
            hp = _hyper(spec, defaults)
            ins = [nodes[i] for i in spec.nindex_in]
            x, p, kind = ins[0], f32(params.get(spec.name, {})), spec.type
            if kind == "embed":
                out = p["wmat"][x]
            elif kind == "layernorm":
                out = _layernorm(x, p, hp)
            elif kind == "mha":
                out = _mha(x, p, hp)
            elif kind == "ffn":
                out = _ffn(x, p, hp)
            elif kind == "add":
                out = sum(ins[1:], ins[0])
            elif kind == "seqfc":
                out = _linear(x, p)
            elif kind == "lmloss":
                out = jax.nn.log_softmax(x, axis=-1)
            else:
                raise ValueError(f"toy_lm.py has no layer kind {kind!r} "
                                 f"(layer {spec.name!r})")
            nodes[spec.nindex_out[0]] = out
    return nodes[layers[-1].nindex_out[0]]


def make_loss_fn(layers, defaults):
    """jit-able ``(params, tokens, label) -> mean token cross-entropy``."""
    def loss(params, tokens, label):
        logp = forward(layers, defaults, params, tokens)
        picked = jnp.take_along_axis(logp, label[..., None], axis=-1)
        return -jnp.mean(picked)
    return loss


def _ids(a):
    a = np.asarray(a)
    return a.reshape(a.shape[0], -1).astype(np.int32)


# -- the contract ---------------------------------------------------------


def needs_initial_params(kind: str) -> bool:
    return kind == "train_loss"


def check(kind: str, view: dict):
    if kind != "train_loss":
        raise ValueError(f"references/toy_lm.py has no check {kind!r}")
    batch = view["batch0"]
    label = batch.label if batch.host_label is None else batch.host_label
    fn = jax.jit(make_loss_fn(view["layers"], view["defaults"]))
    want = float(fn(view["params0"], _ids(batch.data), _ids(label)))
    got, tol = view["warm_losses"][0], LOSS_TOL[view["dtype"]]
    ok = math.isfinite(want) and abs(got - want) <= tol
    return ok, {"check": "train_loss", "program": got, "reference": want,
                "abs_diff": abs(got - want), "tolerance": tol}


def forward_flops_per_row(layers, defaults, positions: int) -> float:
    """Operations of one row's forward pass: two per multiply-add of every
    projection (mha's four, ffn's two, seqfc) and of the two attention
    products, q k' and p v, over the pairs of positions the layer attends
    (S (S + 1) / 2 when causal: the masked half is no work the model
    needs). The embedding is a gather and the norms, the softmax, gelu
    and the adds are bandwidth: none counts."""
    s, width, total = positions, {}, 0.0
    for spec in layers:
        hp = _hyper(spec, defaults)
        e = width.get(spec.nindex_in[0])
        out = e
        if spec.type in ("embed", "seqfc"):
            out = int(hp["nhidden"])
        if spec.type == "seqfc":
            total += 2.0 * s * e * out
        elif spec.type == "mha":
            pairs = s * (s + 1) / 2 if int(hp.get("causal", 0)) else s * s
            total += 4 * 2.0 * s * e * e + 2 * 2.0 * pairs * e
        elif spec.type == "ffn":
            total += 2 * 2.0 * s * e * int(hp.get("nhidden") or 4 * e)
        width[spec.nindex_out[0]] = out
    return total


def train_step_flops(view: dict) -> float:
    """Forward, and a backward of two products per forward product (dX
    and dW): three times the forward. Nothing is taken off for the layer
    that reads the data, as the convnets' count does for its dX: here it
    is the embedding, whose forward is no product and counted nothing."""
    positions = int(np.shape(view["batch0"].data)[-1])
    return 3.0 * view["rows"] * forward_flops_per_row(
        view["layers"], view["defaults"], positions)
