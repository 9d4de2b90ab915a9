"""Reference module ``lfm2_8b_a1b``: the plain float32 reference,
``correct`` and operation count of LFM2-8B-A1B (LiquidAI, ``model_type``
``lfm2_moe``: a gated short convolution mixes positions in the layers
``layer_types`` names ``conv``, grouped-query attention with an RMS norm
on each head's q and k in those it names ``full_attention``; the first
``num_dense_layers`` take a SwiGLU feed-forward, the rest 32 experts
chosen 4 a position by sigmoid scores plus a selection bias, without a
shared one) as one chip of a group of 4 trains it, as a configuration
brings them to ``benchmarks/run.py`` (its header has the contract).

Written from the keys of the model's published ``config.json``, which the
configuration file repeats, in plain ``jax.numpy``: no layer class, no
attention kernel, no grouped product, no mixed precision, nothing
imported from the program or from another configuration's reference.
From the program it takes what a checkpoint reader would — the weights
by layer name, in the program's layouts (the names are those
``tools/gen_joyai_conf.py`` writes) — and, through the ``trainer`` handle
of the view, what a checkpoint holds beside them: the initial weights,
the routers' selection bias, Adam's first moment around one more step of
the timed path's own ``update``, that step's train metric and the expert
layers' own counters.

With ``x`` a position's vector, ``RMS(v) = v / sqrt(mean(v^2) + eps) *
g`` (``eps = norm_eps``), no bias anywhere:

* block: ``h = x + op(RMS(x))``, ``op`` by ``layer_types``; then ``h +
  ffn(RMS(h))``; the stack's output through a final RMS norm and the
  untied head; mean token cross-entropy.
* ``conv`` (the gated short convolution, ``L = conv_L_cache``): ``[B ; C
  ; x~] = x W_in`` (split in that order); ``u = B * x~``; ``v[t] =
  sum_{i<L} w[i] u[t - (L-1) + i]`` with ``u[<0] = 0``, depthwise over
  the channels; ``y = (C * v) W_out``.
* ``full_attention``: ``H`` query heads of ``d = hidden_size / H``,
  ``H_kv`` key/value heads; ``q = x W_q``, ``k = x W_k``, ``v = x W_v``;
  each head's q and k through an RMS norm over its ``d`` features, one
  gain vector for q and one for k; then the rotary on halves
  (``rotate_half``) over the whole head at ``rope_theta``; query head
  ``h`` reads key/value head ``h // (H / H_kv)``; causal softmax of ``q.k
  / sqrt(d)``; ``y = concat_h(o_h) W_o`` — an explicit masked softmax a
  head at a time.
* dense: ``(silu(x W_g) * (x W_u)) W_d``, ``intermediate_size`` wide.
* experts: ``s = sigmoid(x W_r)`` over all ``num_experts_published``;
  chosen = top ``num_experts_per_tok`` of ``s + b``, ``b`` the selection
  bias; ``g_i = s_i / (sum of the chosen s + 1e-6) *
  routed_scaling_factor``; ``y = sum over the chosen experts THIS CHIP
  HOLDS of g_i E_i(x)``, ``E_i`` SwiGLU ``moe_intermediate_size`` wide —
  every held expert densely over all positions under its gate. After a
  step ``b <- b - bias_update_rate * sign(load - mean load)`` over all
  experts.

Every product runs under ``jax.default_matmul_precision("highest")``. At
full width beside a trainer that holds 8.7 GB the reference computes
STAGE BY STAGE — one stage's weights on the device at a time, every
stage's input kept on the host, the backward by ``vjp`` a stage —
attention one head at a time, the experts one at a time.

``VARIANT`` names a planted fault (the variant modules under
``tests/benchmarks/data/lfm2_controls/`` set it): every control has to
come out ``"correct": false``.
"""

from __future__ import annotations

import math
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

#: the planted fault, ``None`` for the reference itself:
#: ``float8`` every product's operands rounded to float8 (e4m3);
#: ``conv_ahead`` the convolution's taps moved one position on, so that
#: position t reads u[t + 1]; ``no_bias`` the selection bias left out of
#: the choice
VARIANT = None

#: Adam as ``cxxnet_tpu/optim.py`` has it (reference adam_updater): decay
#: rates 0.1 / 0.001, the step ``lr sqrt(fix2) / fix1 * m1 / (sqrt(m2) +
#: 1e-8)``
ADAM_D1, ADAM_D2, ADAM_EPS = 0.1, 0.001, 1e-8

#: the standard deviation of the selection bias the check plants for its
#: comparison of gradients (scores are sigmoids, about 0.5 +- 0.2)
PLANTED_BIAS = 0.1

#: the family's term in the gates' denominator
GATE_EPS = 1e-6

#: the limits of ``check("train_steps")``, by the program's compute
#: dtype; each stands between the largest reading of sound runs and the
#: smallest of the float8 control's, with room on both sides. bfloat16 —
#: the cell, on a TPU v5e (PERF.md section 6 has every reading): eight
#: sound readings (five runs through ``run.py``, three seeds of
#: ``lfm2_controls/readings.py``) and the float8 control on one of them:
#:   a step's loss, |program - reference|: sound 2.4e-5 ... 2.2e-3 (the
#:   third step's the largest); float8 6.3e-4 / 9.4e-3 / 2.3e-2 on steps
#:   1 / 2 / 3;
#:   the seventh step's loss and the metric's cross-entropy: sound 7.5e-5
#:   ... 1.5e-3; float8 5.6e-2;
#:   a leaf's gradient norm, relative, worst leaf of a group: sound
#:   2.5e-4 ... 5.9e-3 (a norm's gain the largest in every block, the
#:   routers 4.6e-3, the taps 1.3e-3, the in-projections 4.2e-4); float8
#:   3.8e-2 (embedding) ... 0.44 (block 1), the routers 0.30.
#: Those readings were made at a selection-bias rate of 0.001; ten more
#: sound runs at the configuration's 0.01 read 3.2e-5 ... 1.6e-3 (a
#: step's loss), 7.1e-5 ... 1.2e-3 (the seventh step's) and at most
#: 6.3e-3 (a gradient group), the float8 control on one seed 2.3e-3 /
#: 7.9e-3 / 2.5e-2, 8.5e-2 and 2.2e-2 (embedding) ... 0.34 (block 1).
#: The other two controls pass limits by more: the taps a position on
#: 1.9e-2 on the first step's loss and 8.3 on the seventh's, the bias
#: left out 1.4e-2 on the seventh's loss and 0.12 ... 0.31 on every
#: group of gradients (its three steps start from a zero bias).
#: float32 — the tests' toy size on the CPU: sound under 6e-6
#: (losses) and 7e-7 (gradient norms); every control over a limit by at
#: least one number.
LIMITS = {
    "float32": {"loss_abs": 5e-5, "probe_loss_abs": 5e-5,
                "grad_norm_rel": 1e-3},
    "bfloat16": {"loss_abs": 5e-3, "probe_loss_abs": 5e-3,
                 "grad_norm_rel": 1.5e-2},
}


# -- the pieces -----------------------------------------------------------


def _q8(a):
    """Rounded to float8 on the way in; the gradient passes straight
    through."""
    return a + jax.lax.stop_gradient(
        a.astype(jnp.float8_e4m3fn).astype(jnp.float32) - a)


def mm(spec, a, b):
    """Every product of the reference: ``einsum`` in float32 at the
    highest precision; under the ``float8`` control both operands are
    rounded to float8 first."""
    if VARIANT == "float8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rms(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma


def swiglu(x, p):
    h = jax.nn.silu(mm("...e,ef->...f", x, p["g"]["wmat"])) \
        * mm("...e,ef->...f", x, p["h"]["wmat"])
    return mm("...f,fe->...e", h, p["o"]["wmat"])


def delayed(u, lag):
    """``u`` (B, S, E) read ``lag`` positions back along S: zeros where
    that is before the row's start (after its end for a negative
    ``lag``)."""
    if lag == 0:
        return u
    S = u.shape[1]
    if lag > 0:
        return jnp.pad(u, ((0, 0), (lag, 0), (0, 0)))[:, :S]
    return jnp.pad(u, ((0, 0), (0, -lag), (0, 0)))[:, -lag:]


def short_conv(p, x, c):
    """The gated short convolution on (B, S, E)."""
    L = c["conv_L_cache"]
    bcx = mm("bse,ekf->bskf", x, p["in_proj"]["wmat"])
    gate_b, gate_c, xt = bcx[:, :, 0], bcx[:, :, 1], bcx[:, :, 2]
    u = gate_b * xt
    w = p["conv"]["wmat"]
    ahead = 1 if VARIANT == "conv_ahead" else 0
    v = sum(w[i] * delayed(u, L - 1 - i - ahead) for i in range(L))
    return mm("bsf,fe->bse", gate_c * v, p["out_proj"]["wmat"])


def rotary(x, theta: float):
    """(B, S, H, d) rotated whole on halves: feature ``i`` pairs with ``i
    + d/2`` and turns by ``t theta^(-2i/d)`` at position ``t``."""
    S, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-2.0 * np.arange(half, dtype=np.float64) / (2 * half))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(p, x, c):
    """Causal grouped-query attention on (B, S, E), one query head at a
    time."""
    S, eps, theta = x.shape[1], c["norm_eps"], float(c["rope_theta"])
    H, Hkv = p["q"]["wmat"].shape[1], p["k"]["wmat"].shape[1]
    d = p["q"]["wmat"].shape[2]
    k = rotary(rms(mm("bse,ehd->bshd", x, p["k"]["wmat"]),
                   p["knorm"]["gamma"], eps), theta)
    v = mm("bse,ehd->bshd", x, p["v"]["wmat"])
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(w_q, w_o, kv):
        q = mm("bse,ed->bsd", x, w_q)[:, :, None, :]
        q = rotary(rms(q, p["qnorm"]["gamma"], eps), theta)[:, :, 0]
        s = mm("bqd,bkd->bqk", q, k[:, :, kv]) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("bsd,de->bse", mm("bqk,bkd->bqd", pr, v[:, :, kv]), w_o)
    total, _ = jax.lax.scan(
        lambda acc, a: (acc + head(*a), None), jnp.zeros_like(x),
        (jnp.moveaxis(p["q"]["wmat"], 1, 0), p["o"]["wmat"],
         jnp.asarray(np.arange(H) // (H // Hkv), jnp.int32)))
    return total


def route(p, sel_bias, x, c):
    """Gates ``(N, X)`` — zero where an expert was not chosen — and the
    load of every expert, for positions ``x`` (N, E)."""
    k = c["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.einsum("ne,ex->nx", x, p["router"]["wmat"],
                                  precision=jax.lax.Precision.HIGHEST))
    biased = s if VARIANT == "no_bias" \
        else s + jax.lax.stop_gradient(sel_bias)
    kth = jnp.sort(biased, axis=1)[:, -k][:, None]
    chosen = biased >= kth
    gates = jnp.where(chosen, s, 0.0)
    if c["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + GATE_EPS)
    gates = gates * c["routed_scaling_factor"]
    return gates, jnp.sum(chosen.astype(jnp.float32), axis=0)


def experts(p, sel_bias, x, c):
    """The expert layer's partial sum on (B, S, E) and the new bias."""
    B, S, E = x.shape
    xf = x.reshape(B * S, E)
    gates, load = route(p, sel_bias, xf, c)
    first, held = c["expert_first"], c["num_experts"]

    @jax.checkpoint
    def one(w):
        g, wg, wh, wo = w
        y = mm("nf,fe->ne", jax.nn.silu(mm("ne,ef->nf", xf, wg))
               * mm("ne,ef->nf", xf, wh), wo)
        return g[:, None] * y
    out, _ = jax.lax.scan(
        lambda acc, w: (acc + one(w), None), jnp.zeros_like(xf),
        (gates[:, first:first + held].T, p["g"]["wmat"], p["h"]["wmat"],
         p["o"]["wmat"]))
    new_bias = sel_bias - c["bias_update_rate"] * jnp.sign(
        load - jnp.mean(load))
    return out.reshape(B, S, E), new_bias


def head_loss(h, w_head, target, chunk=2048):
    """Mean over the positions of -log softmax(h W)[target], a slice of
    positions at a time."""
    B, S, E = h.shape
    hs, lab = h.reshape(B * S, E), target.reshape(B * S)
    if hs.shape[0] <= chunk:
        chunk = hs.shape[0]
    pad = (-hs.shape[0]) % chunk
    hs = jnp.pad(hs, ((0, pad), (0, 0)))
    lab = jnp.pad(lab, (0, pad), constant_values=-1)

    @jax.checkpoint
    def part(a):
        hc, lc = a
        lp = jax.nn.log_softmax(mm("ne,ev->nv", hc, w_head), axis=-1)
        picked = jnp.take_along_axis(lp, jnp.maximum(lc, 0)[:, None],
                                     axis=1)[:, 0]
        return -jnp.sum(jnp.where(lc >= 0, picked, 0.0))
    sums = jax.lax.map(part, tuple(
        a.reshape((-1, chunk) + a.shape[1:]) for a in (hs, lab)))
    return jnp.sum(sums) / (B * S)


# -- the model as a chain of stages ----------------------------------------


def stages(c):
    """``[(name, {part: layer}, fn)]``: the model as a chain. ``fn(p,
    bias, x, tokens, target) -> (y, loss, new_bias)`` with ``p`` the
    weights by PART, so that stages which differ in nothing but their
    weights compile once; ``bias`` the stage's router bias (or None)."""
    eps, out = c["norm_eps"], []

    def embed(p, bias, x, tokens, target):
        return p["embed"]["wmat"][tokens], 0.0, bias

    def conv_half(p, bias, x, tokens, target):
        return x + short_conv(p["op"], rms(x, p["ln1"]["gamma"], eps),
                              c), 0.0, bias

    def attn_half(p, bias, x, tokens, target):
        return x + attention(p["op"], rms(x, p["ln1"]["gamma"], eps),
                             c), 0.0, bias

    def dense_half(p, bias, x, tokens, target):
        return x + swiglu(rms(x, p["ln2"]["gamma"], eps), p["ffn"]), 0.0, \
            bias

    def expert_half(p, bias, x, tokens, target):
        y, nb = experts(p["moe"], bias, rms(x, p["ln2"]["gamma"], eps), c)
        return x + y, 0.0, nb

    def head(p, bias, x, tokens, target):
        h = rms(x, p["norm"]["gamma"], eps)
        return h, head_loss(h, p["head"]["wmat"], target), bias

    out.append(("embed", {"embed": "tok_embed"}, embed))
    for i, kind in enumerate(c["layer_types"]):
        pre = f"b{i}"
        if kind == "conv":
            out.append((pre + "_conv", {"ln1": pre + "_ln1",
                                        "op": pre + "_conv"}, conv_half))
        else:
            out.append((pre + "_attn", {"ln1": pre + "_ln1",
                                        "op": pre + "_attn"}, attn_half))
        if i < c["num_dense_layers"]:
            out.append((pre + "_ffn", {"ln2": pre + "_ln2",
                                       "ffn": pre + "_ffn"}, dense_half))
        else:
            out.append((pre + "_moe", {"ln2": pre + "_ln2",
                                       "moe": pre + "_moe"}, expert_half))
    out.append(("head", {"norm": "final_norm", "head": "lm_head"}, head))
    return out


def _moe_layer(own):
    return own.get("moe")


class Model:
    """The stages' functions compiled once each way, and the
    stage-by-stage walk: weights, Adam's moments and every stage's input
    live on the host (``numpy``), one stage's on the device while it
    runs."""

    def __init__(self, c):
        self.c = c
        self.stages = stages(c)
        self._jits = {}

    def _fns(self, i):
        fn = self.stages[i][2]
        if fn not in self._jits:
            def bwd(p, bias, x, tokens, target, gy):
                (y, loss, nb), vjp = jax.vjp(
                    lambda p_, x_: fn(p_, bias, x_, tokens, target), p, x)
                return vjp((gy, jnp.ones_like(loss),
                            jnp.zeros_like(nb) if nb is not None else None))
            self._jits[fn] = jax.jit(fn), jax.jit(bwd)
        return self._jits[fn]

    @staticmethod
    def _weights(params, own):
        return {part: params[layer] for part, layer in own.items()}

    def forward(self, params, biases, tokens, label):
        """-> (the cross-entropy, every stage's input, new biases)."""
        x, xs, ce, new_biases = np.zeros((), np.float32), [], 0.0, {}
        with jax.default_matmul_precision("highest"):
            for i, (name, own, fn) in enumerate(self.stages):
                xs.append(x)
                moe = _moe_layer(own)
                y, loss, nb = self._fns(i)[0](
                    self._weights(params, own),
                    biases[moe] if moe else None, x, tokens, label)
                x = np.asarray(y)
                ce += float(loss)
                if moe:
                    new_biases[moe] = np.asarray(nb)
        return ce, xs, new_biases

    def backward(self, params, biases, tokens, label, xs):
        """Gradients of the cross-entropy by layer name (host)."""
        grads = {}
        gy = np.zeros(xs[-1].shape, np.float32)
        with jax.default_matmul_precision("highest"):
            for i in reversed(range(len(self.stages))):
                name, own, fn = self.stages[i]
                moe = _moe_layer(own)
                gp, gx = self._fns(i)[1](
                    self._weights(params, own),
                    biases[moe] if moe else None, xs[i], tokens, label, gy)
                gy = np.asarray(gx)
                for part, layer in own.items():
                    grads[layer] = jax.tree_util.tree_map(np.asarray,
                                                          gp[part])
        return grads


@jax.jit
def _adam_leaf(w, g, a, b, lr_t):
    a = a + ADAM_D1 * (g - a)
    b = b + ADAM_D2 * (jnp.square(g) - b)
    return w - lr_t * a / (jnp.sqrt(b) + ADAM_EPS), a, b


def adam_step(params, grads, m1, m2, t, lr):
    """One step of the program's Adam on host arrays, a leaf at a time
    on the device: returns the new (params, m1, m2)."""
    fix1, fix2 = 1.0 - (1.0 - ADAM_D1) ** t, 1.0 - (1.0 - ADAM_D2) ** t
    lr_t = np.float32(lr * math.sqrt(fix2) / fix1)
    flat = [jax.tree_util.tree_flatten(t_) for t_ in (params, grads, m1, m2)]
    outs = [tuple(np.asarray(v) for v in _adam_leaf(w, g, a, b, lr_t))
            for w, g, a, b in zip(*(leaves for leaves, _ in flat))]
    return tuple(jax.tree_util.tree_unflatten(flat[0][1],
                                              [o[k] for o in outs])
                 for k in range(3))


def train_steps(model, params, biases, tokens, label, lr, steps=3):
    """The cross-entropy of each of ``steps`` steps of Adam from
    ``params``, the routers' biases updated after each as the program
    updates them."""
    zeros = lambda t: jax.tree_util.tree_map(np.zeros_like, t)
    m1, m2 = zeros(params), zeros(params)
    losses = []
    for t in range(1, steps + 1):
        ce, xs, new_biases = model.forward(params, biases, tokens, label)
        losses.append(ce)
        if t == steps:
            break
        grads = model.backward(params, biases, tokens, label, xs)
        params, m1, m2 = adam_step(params, grads, m1, m2, t, lr)
        biases = new_biases
    return losses


def grad_norms(grads):
    """{"layer/leaf/...": l2 norm}."""
    out = {}
    for layer, tree in grads.items():
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            key = layer + "/" + "/".join(str(getattr(k, "key", k))
                                         for k in path)
            out[key] = float(np.sqrt(np.sum(np.square(
                np.asarray(leaf, np.float64)))))
    return out


def group_of(key):
    """Which line of ``compared`` a leaf's gradient norm belongs to: the
    short convolutions' taps and in-projections by name, the routers,
    the embedding, the head (with the final norm), else its block."""
    layer, rest = key.split("/", 1)
    if rest.startswith("router"):
        return "routers"
    if layer.endswith("_conv") and rest.startswith("conv"):
        return "conv_taps"
    if layer.endswith("_conv") and rest.startswith("in_proj"):
        return "conv_in"
    if layer == "tok_embed":
        return "embed"
    if layer in ("lm_head", "final_norm"):
        return "head"
    return layer.split("_")[0]          # b0 .. b4


# -- the contract -----------------------------------------------------------


def _ids(a):
    a = np.asarray(a)
    return a.reshape(a.shape[0], -1).astype(np.int32)


def _host(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), tree)


def moe_names(c):
    return [f"b{i}_moe" for i in range(c["num_dense_layers"],
                                       c["num_hidden_layers"])]


def initial_params(tr, seed):
    """The weights the trainer started from, on the host: the program's
    initialiser under the conf's seed, run once more."""
    return _host(jax.jit(tr.net.init)(jax.random.PRNGKey(seed))[0])


def timed_step(tr, batch):
    """One more step of the timed path's own ``update`` on ``batch`` ->
    ``(the step's loss, its train metric's cross-entropy, the l2 norm of
    the step's gradient by layer and leaf)``. Adam's first moment is ``m1
    <- m1 + d1 (g - m1)``, so the step's gradient is what it did to
    ``m1``."""
    before = _host(tr.opt_state["m1"])
    tr.train_metric_report()
    type(tr).update(tr, batch)
    loss = float(tr.last_loss)
    said = [float(v) for v in re.findall(r"seq_logloss:(\S+)",
                                         tr.train_metric_report())]

    def norm(after, b):            # one leaf on the host at a time
        g = (np.asarray(after, np.float64) - (1.0 - ADAM_D1) * b) / ADAM_D1
        return float(np.sqrt(np.sum(np.square(g))))
    return loss, said[0] if said else float("nan"), grad_norms(
        jax.tree_util.tree_map(norm, tr.opt_state["m1"], before))


def check(kind: str, view: dict):
    if kind != "train_steps":
        raise ValueError(f"references/lfm2_8b_a1b.py has no check {kind!r}")
    c, tr = view["config"], view["trainer"]
    lim = LIMITS[view["dtype"]]
    batch = view["batch0"]
    label = _ids(batch.label if batch.host_label is None
                 else batch.host_label)
    tokens = _ids(batch.data)
    lr = float(dict(view["defaults"]).get("eta", 0.01))
    model = Model(c)
    said, ok = {"check": kind, "variant": VARIANT}, True
    t_mark, seconds = [time.perf_counter()], {}

    def mark(name):
        now = time.perf_counter()
        seconds[name], t_mark[0] = now - t_mark[0], now

    def hold(name, diff, limit):
        nonlocal ok
        said[name], said[name + "_limit"] = diff, limit
        ok = ok and math.isfinite(diff) and diff <= limit

    # 1. three steps of Adam from the initial weights, the routers' bias
    #    from zero, against the losses the timed path's first three steps
    #    gave
    seed = int(dict(view["defaults"]).get("seed", 0))
    zero_bias = {n: np.zeros((c["num_experts_published"],), np.float32)
                 for n in moe_names(c)}
    params0 = initial_params(tr, seed)
    mark("initial_weights")
    losses = train_steps(model, params0, zero_bias, tokens, label, lr)
    del params0
    mark("three_steps")
    for t, want in enumerate(losses):
        got = view["warm_losses"][t]
        said[f"loss_step{t + 1}_program"] = got
        said[f"loss_step{t + 1}_reference"] = want
        hold(f"loss_step{t + 1}_abs_diff", abs(got - want), lim["loss_abs"])
    # 2. one more step of the timed path, at the weights as the warm-up
    #    left them: its loss and every leaf's gradient norm against the
    #    reference's forward and backward at the same weights — under a
    #    PLANTED selection bias (a tenth of a standard normal, seeded; the
    #    warm-up's own is a few thousandths), so that the choice with the
    #    bias and the gates without it are both at stake; the routers get
    #    their own bias back afterwards
    rng = np.random.RandomState(seed)
    biases = {n: (PLANTED_BIAS * rng.standard_normal(
        c["num_experts_published"])).astype(np.float32)
        for n in moe_names(c)}
    now = {name: _host(leaves) for name, leaves in tr.params.items()}
    own_bias = {n: tr.net_state[n]["sel_bias"] for n in biases}
    for n, b in biases.items():
        tr.net_state[n]["sel_bias"] = jax.device_put(
            b, own_bias[n].sharding)
    p_loss, p_metric, got = timed_step(tr, batch)
    for n, b in own_bias.items():
        tr.net_state[n]["sel_bias"] = b
    mark("program_probe")
    r_ce, xs, _ = model.forward(now, biases, tokens, label)
    grads = model.backward(now, biases, tokens, label, xs)
    del now, xs
    mark("reference_probe")
    said["probe_loss_program"] = p_loss
    said["probe_loss_metric_program"] = p_metric
    said["probe_loss_reference"] = r_ce
    hold("probe_loss_abs_diff", abs(p_loss - r_ce), lim["probe_loss_abs"])
    hold("probe_loss_metric_abs_diff", abs(p_metric - r_ce),
         lim["probe_loss_abs"])
    want = grad_norms(grads)
    del grads
    worst, raw = {}, {}
    for key, ref in want.items():
        rel = abs(got[key] - ref) / max(ref, 1e-30)
        if rel > 1e-2:
            raw[key] = [got[key], ref]
        g = group_of(key)
        if rel >= worst.get(g, (-1.0, ""))[0]:
            worst[g] = (rel, key)
    view["say"](grad_norms_program_reference_apart_by_over_a_hundredth=raw)
    for g, (rel, key) in sorted(worst.items()):
        said[f"grad_norm_{g}_worst_leaf"] = key
        hold(f"grad_norm_{g}_rel_diff", rel, lim.get(
            "grad_norm_rel_" + g, lim["grad_norm_rel"]))
    # 3. no pair of a held expert was left out
    dropped = float(sum(np.asarray(tr.net_state[n]["stats"])[2]
                        for n in moe_names(c)))
    hold("moe_pairs_dropped", dropped, 0.0)
    view["say"](reference_check_seconds=seconds)
    return ok, said


# -- the operation count ------------------------------------------------------


def layers_of(c, kind: str) -> int:
    return sum(1 for k in c["layer_types"] if k == kind)


def causal_pairs(positions: int) -> float:
    return positions * (positions + 1) / 2


def shortconv_params(c) -> float:
    """Parameters of a short convolution's products a position passes
    through: the in-projection (3 E x E), the taps (L x E, one
    multiply-add a tap and channel) and the out-projection (E x E)."""
    E = c["hidden_size"]
    return float(4 * E * E + c["conv_L_cache"] * E)


def shortconv_flops(c, positions: int) -> float:
    """The short convolutions' products of ALL their layers on one row,
    forward: ``2 (4 E^2 + L E)`` a position a layer. The gates'
    elementwise products are bandwidth and are not counted."""
    return 2.0 * positions * shortconv_params(c) * layers_of(c, "conv")


def attention_flops(c, positions: int) -> float:
    """The causal products of ALL attention layers on one row, forward:
    q.k and p.v over the head's ``E / H`` features, two operations a
    multiply-add, over the S (S + 1) / 2 pairs a causal head attends."""
    H = c["num_attention_heads"]
    d = c["hidden_size"] // H
    return 2.0 * causal_pairs(positions) * 2 * d * H \
        * layers_of(c, "full_attention")


def expert_pair_flops(c) -> float:
    """One (position, expert) pair through one routed expert, forward."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def matrix_params_per_position(c) -> float:
    """Parameters of the matrix products one position passes through,
    forward, the held experts' by the EXPECTED pairs a position (``topk x
    held / all``) so that the count does not move with the routing. The
    embedding is a gather and the norms are bandwidth: neither counts."""
    E, H = c["hidden_size"], c["num_attention_heads"]
    d, Hkv = E // H, c["num_key_value_heads"]
    attn = 2 * E * H * d + 2 * E * Hkv * d
    pairs = c["num_experts_per_tok"] * c["num_experts"] \
        / c["num_experts_published"]
    moe = E * c["num_experts_published"] \
        + 3 * E * c["moe_intermediate_size"] * pairs
    n_dense = c["num_dense_layers"]
    return float(E * c["vocab_size"]
                 + layers_of(c, "conv") * shortconv_params(c)
                 + layers_of(c, "full_attention") * attn
                 + n_dense * 3 * E * c["intermediate_size"]
                 + (c["num_hidden_layers"] - n_dense) * moe)


def step_flops(c, rows: int, positions: int) -> float:
    """Forward, and a backward of two products per forward product:
    6 x parameters a position passes through, plus the causal attention
    products forward once and backward twice."""
    return rows * (6.0 * positions * matrix_params_per_position(c)
                   + 3.0 * attention_flops(c, positions))


def train_step_flops(view: dict) -> float:
    positions = int(view["config"]["input_shape"][-1])
    return step_flops(view["config"], view["rows"], positions)
