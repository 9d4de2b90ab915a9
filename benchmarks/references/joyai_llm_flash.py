"""Reference module ``joyai_llm_flash``: the plain float32 reference,
``correct`` and operation count of JoyAI-LLM-Flash (48B-A2.7B, the
DeepSeek-V3 family: latent attention, one leading dense layer, 256
sigmoid-routed experts of which 8 a token plus a shared one, one
multi-token-prediction module) as one chip of an expert-parallel group
trains it, as a configuration brings them to ``benchmarks/run.py`` (its
header has the contract).

Written from the keys of the model's published ``config.json``, which the
configuration file repeats, in plain ``jax.numpy``: no layer class, no
attention kernel, no grouped product, no mixed precision, nothing
imported from the program. From the program it takes what a checkpoint
reader would — the weights by layer name, in the program's layouts (the
names are those ``tools/gen_joyai_conf.py`` writes) — and, through the
``trainer`` handle of the view, what a checkpoint holds beside them: the
initial weights (the program's initialiser under its seed), Adam's first
moment around one more step of the timed path's own ``update`` — which
is that step's gradient, leaf by leaf — that step's train metric (each
head's loss) and the routers' selection bias.

With ``x`` a position's vector, ``RMS(v) = v / sqrt(mean(v^2) + eps) * g``:

* attention: ``c_q = RMS(x W_qa)``; per head ``[q_nope ; q_rope] = c_q
  W_qb``; ``[c_kv ; k_rope] = x W_kva``; ``c_kv <- RMS(c_kv)``; per head
  ``[k_nope ; v] = c_kv W_kvb``; ``q_rope``, ``k_rope`` rotated on
  interleaved pairs with ``rope_theta``, ``k_rope`` one vector for all
  heads; causal ``softmax((q_nope.k_nope + q_rope.k_rope) / sqrt(d_nope +
  d_rope)) v``; ``W_o``. No bias.
* feed-forward: ``(silu(x W_g) * (x W_u)) W_d``.
* experts: ``s = sigmoid(x W_r)`` over all ``n_routed_experts_published``
  experts (``n_routed_experts`` is how many this chip holds, from
  ``expert_first`` on);
  chosen = top ``num_experts_per_tok`` of ``s + b``; ``g_i = s_i / sum of
  the chosen s * routed_scaling_factor``; ``y = shared(x) + sum over the
  chosen experts THIS CHIP HOLDS of g_i E_i(x)`` — every held expert runs
  densely over all positions under its gate (zero where it was not
  chosen): the plainest form, and no relative of the program's sorted
  grouped products. After a step ``b_i <- b_i - rate * sign(load_i - mean
  load)``.
* block: ``x + attn(RMS(x))`` then ``+ ffn_or_experts(RMS(.))``; the
  stack's output through a final RMS norm and the head; mean token
  cross-entropy.
* the multi-token-prediction module: ``[RMS(h) ; RMS(Emb(next token))]
  W_eh``, one expert block, a norm, the main model's head; its loss is
  against the label one position on, the row's last position left out;
  objective ``L_main + mtp_loss_weight * L_mtp``.

Every product runs under ``jax.default_matmul_precision("highest")``.
At full width beside a trainer that holds 10.9 GB the reference computes
STAGE BY STAGE — one stage's weights on the device at a time, every
stage's input kept on the host, the backward by ``vjp`` a stage — and
attention one head at a time, the head's loss a slice of positions at a
time, so that it stays under 2 GB of the device.

``VARIANT`` names a planted fault (the variant modules beside this one
set it): every control has to come out ``"correct": false``.
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
#: ``weights_off`` the weights handed in off by 1 %; ``top7`` one expert
#: fewer a token; ``no_shared`` the shared expert left out;
#: ``no_scaling`` ``routed_scaling_factor`` left out; ``bias_in_weights``
#: the selection bias entering the gates; ``rope_halves`` rotary on
#: halves instead of interleaved pairs; ``scale_nope`` scores scaled by
#: 1/sqrt(d_nope)
VARIANT = None

#: Adam as ``cxxnet_tpu/optim.py`` has it (reference adam_updater): decay
#: rates 0.1 / 0.001, the step ``lr sqrt(fix2) / fix1 * m1 / (sqrt(m2) +
#: 1e-8)``
ADAM_D1, ADAM_D2, ADAM_EPS = 0.1, 0.001, 1e-8

#: the standard deviation of the selection bias the check plants for its
#: comparison of gradients (scores are sigmoids, about 0.5 +- 0.2)
PLANTED_BIAS = 0.1

#: the limits of ``check("train_steps")``, by the program's compute
#: dtype; each stands between the largest reading of sound runs and the
#: smallest of the controls it is there to catch, with room on both
#: sides. bfloat16 — the cell, on a TPU v5e (my chip runs, PR 28; PERF.md
#: section 6 has every reading): set from twelve seeds in one process and
#: every control on the first, when the gradients came from a second
#: program; read again, as the check stands, on the cell's own seven runs
#: through ``run.py`` and on four controls (``readings.py``):
#:   a step's loss, |program - reference|: sound 3.1e-6 ... 2.6e-3 (the
#:   third step's the largest); the float8 control 5.8e-3 / 0.183 / 0.264
#:   on steps 1 / 2 / 3, weights off by 1 % 2.1e-2 ... 7.9e-2;
#:   the seventh step's loss and each head's own: sound 2.4e-7 ...
#:   1.3e-3; the bias in the gates 1.42e-2 (main) and 5.6e-3 (the second
#:   head), weights off 0.069 ... 0.126, float8 0.60 ... 3.0, top-7 for
#:   top-8 6.5e-3 on the second head alone;
#:   a leaf's gradient norm, relative, worst leaf of a group: sound
#:   9.5e-5 ... 6.0e-3 over the blocks, embedding, head and W_eh, 2.7e-3
#:   ... 1.24e-2 over the routers (a few positions choose another eighth
#:   expert under bfloat16); top-7 for top-8 5.2e-2 ... 8.2e-2 over three
#:   expert blocks and the module and 9.1e-2 over the routers, and that
#:   control passes every limit on a step's loss — the gradients are what
#:   catches it; the bias in the gates 2.5e-2 ... 9.0e-2 and 0.165;
#:   weights off 5.1e-2 ... 0.51; float8 0.51 ... 1.7.
#: float32 — the tests' toy size on the sandbox's CPU: sound under 4e-6
#: and 5e-7, every control over 4e-4 and 1e-2 by at least one.
LIMITS = {
    "float32": {"loss_abs": 5e-5, "probe_loss_abs": 5e-5,
                "grad_norm_rel": 1e-3, "grad_norm_rel_routers": 1e-3},
    "bfloat16": {"loss_abs": 8e-3, "probe_loss_abs": 4e-3,
                 "grad_norm_rel": 2e-2, "grad_norm_rel_routers": 4e-2},
}


# -- the pieces -----------------------------------------------------------


def _q8(a):
    """Rounded to float8 on the way in; the gradient passes straight
    through (a cotangent cast to float8 would be flushed to zero, which
    is a fault of another kind than a lower precision)."""
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


def rotary(x, theta, interleaved=True):
    """(B, S, H, D): pairs (2i, 2i+1) turned by ``pos * theta^(-2i/D)``;
    ``interleaved=False`` pairs i with i + D/2 (the fault)."""
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, D // 2, dtype=np.float64) / (D // 2))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(p, x, c):
    """Latent attention on (B, S, E), one head at a time."""
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rkv, eps, theta = c["kv_lora_rank"], c["rms_norm_eps"], c["rope_theta"]
    inter = VARIANT != "rope_halves"
    c_q = rms(mm("bse,er->bsr", x, p["qa"]["wmat"]),
              p["qnorm"]["gamma"], eps)
    kv = mm("bse,er->bsr", x, p["kva"]["wmat"])
    c_kv = rms(kv[..., :rkv], p["kvnorm"]["gamma"], eps)
    k_rope = rotary(kv[..., rkv:][:, :, None, :], theta, inter)[:, :, 0]
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = 1.0 / math.sqrt(dn if VARIANT == "scale_nope" else dn + dr)

    @jax.checkpoint
    def head(w_qb, w_kvb, w_o):
        q = mm("bsr,rd->bsd", c_q, w_qb)
        q_rope = rotary(q[..., dn:][:, :, None, :], theta, inter)[:, :, 0]
        kvb = mm("bsr,rd->bsd", c_kv, w_kvb)
        s = (mm("bqd,bkd->bqk", q[..., :dn], kvb[..., :dn])
             + mm("bqd,bkd->bqk", q_rope, k_rope)) * scale
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("bsd,de->bse", mm("bqk,bkd->bqd", pr, kvb[..., dn:]),
                  w_o)

    total, _ = jax.lax.scan(
        lambda acc, w: (acc + head(*w), None), jnp.zeros_like(x),
        (jnp.moveaxis(p["qb"]["wmat"], 1, 0),
         jnp.moveaxis(p["kvb"]["wmat"], 1, 0), p["o"]["wmat"]))
    return total


def route(p, sel_bias, x, c):
    """Gates ``(N, X)`` — zero where an expert was not chosen — and the
    load of every expert, for positions ``x`` (N, E)."""
    k = c["num_experts_per_tok"] - (1 if VARIANT == "top7" else 0)
    s = jax.nn.sigmoid(jnp.einsum("ne,ex->nx", x, p["router"]["wmat"],
                                  precision=jax.lax.Precision.HIGHEST))
    biased = s + jax.lax.stop_gradient(sel_bias)
    kth = jnp.sort(biased, axis=1)[:, -k][:, None]
    chosen = biased >= kth
    w = biased if VARIANT == "bias_in_weights" else s
    gates = jnp.where(chosen, w, 0.0)
    if c["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=1, keepdims=True)
    if VARIANT != "no_scaling":
        gates = gates * c["routed_scaling_factor"]
    return gates, jnp.sum(chosen.astype(jnp.float32), axis=0)


def experts(p, sel_bias, x, c):
    """The expert layer's partial sum on (B, S, E) and the new bias."""
    B, S, E = x.shape
    xf = x.reshape(B * S, E)
    gates, load = route(p, sel_bias, xf, c)
    first, held = c["expert_first"], c["n_routed_experts"]

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
    if c["n_shared_experts"] and VARIANT != "no_shared":
        out = out + swiglu(xf, p["shared"])
    new_bias = sel_bias - c["bias_update_rate"] * jnp.sign(
        load - jnp.mean(load))
    return out.reshape(B, S, E), new_bias


def attention_half(p, x, c):
    """The first half of a pre-norm block: ``x + attn(RMS(x))``."""
    return x + attention(p["attn"], rms(x, p["ln1"]["gamma"],
                                        c["rms_norm_eps"]), c)


def mlp_half(p, sel_bias, x, c, dense):
    """The second half: ``x + ffn_or_experts(RMS(x))`` and the new bias;
    ``p`` by part: ``ln2`` and ``ffn`` or ``moe``."""
    h = rms(x, p["ln2"]["gamma"], c["rms_norm_eps"])
    if dense:
        return x + swiglu(h, p["ffn"]), sel_bias
    y, new_bias = experts(p["moe"], sel_bias, h, c)
    return x + y, new_bias


def head_loss(h, w_head, target, chunk=2048):
    """Mean over the counted positions of -log softmax(h W)[target]; a
    position whose target is negative is not counted (so a head one
    position on is the same function on another row of targets); a slice
    of positions at a time."""
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
    return jnp.sum(sums) / jnp.sum((target >= 0).astype(jnp.float32))


def targets(label, shift):
    """The row of targets of a head ``shift`` positions on: position i
    against the label at i + shift, the row's last ``shift`` not counted
    (-1)."""
    if not shift:
        return label
    return np.concatenate([label[:, shift:], np.full_like(
        label[:, :shift], -1)], axis=1)


# -- the model as a chain of stages ----------------------------------------


def stages(c):
    """``[(name, {part: layer it owns}, {part: layer it borrows}, fn,
    shift, weight)]``: the model as a chain. ``fn(p, bias, x, tokens,
    target) -> (y, loss, new_bias)`` with ``p`` the weights by PART (so
    that stages which differ in nothing but their weights — the six
    attention halves, the five expert halves, the two heads — are one
    function and compile once), ``bias`` the stage's router bias (or
    None), ``target`` the label row ``shift`` positions on
    (:func:`targets`); the stage's loss counts ``weight`` times. A
    borrowed layer's gradient is added to its owner's: the embedding and
    the head serve both losses."""
    eps, out = c["rms_norm_eps"], []

    def embed(p, bias, x, tokens, target):
        return p["embed"]["wmat"][tokens], 0.0, bias

    def attn_half(p, bias, x, tokens, target):
        return attention_half(p, x, c), 0.0, bias

    def dense_half(p, bias, x, tokens, target):
        return mlp_half(p, bias, x, c, True)[0], 0.0, bias

    def expert_half(p, bias, x, tokens, target):
        y, nb = mlp_half(p, bias, x, c, False)
        return y, 0.0, nb

    def head(p, bias, x, tokens, target):
        h = rms(x, p["norm"]["gamma"], eps)
        return h, head_loss(h, p["head"]["wmat"], target), bias

    def mtp_in(p, bias, x, tokens, target):
        e = p["embed"]["wmat"][target]         # the next token
        cat = jnp.concatenate([rms(x, p["hnorm"]["gamma"], eps),
                               rms(e, p["enorm"]["gamma"], eps)], axis=-1)
        return mm("bsc,ce->bse", cat, p["eh"]["wmat"]), 0.0, bias

    def halves(pre, dense):
        out.append((pre + "_attn", {"ln1": pre + "_ln1",
                                    "attn": pre + "_attn"}, {},
                    attn_half, 0, 1.0))
        out.append((pre + "_mlp", {"ln2": pre + "_ln2", **(
            {"ffn": pre + "_ffn"} if dense else {"moe": pre + "_moe"})}, {},
            dense_half if dense else expert_half, 0, 1.0))
    out.append(("embed", {"embed": "tok_embed"}, {}, embed, 0, 1.0))
    for i in range(c["num_hidden_layers"]):
        halves(f"b{i}", i < c["first_k_dense_replace"])
    out.append(("head", {"norm": "final_norm", "head": "lm_head"}, {}, head,
                0, 1.0))
    if c["num_nextn_predict_layers"]:
        out.append(("mtp_in", {"hnorm": "mtp_hnorm", "enorm": "mtp_enorm",
                               "eh": "mtp_eh"}, {"embed": "tok_embed"},
                    mtp_in, 0, 1.0))
        halves("mtp", False)
        out.append(("mtp_head", {"norm": "mtp_final_norm"},
                    {"head": "lm_head"}, head, 1, c["mtp_loss_weight"]))
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
        fn = self.stages[i][3]
        if fn not in self._jits:
            def bwd(p, bias, x, tokens, target, gy, gl):
                (y, loss, nb), vjp = jax.vjp(
                    lambda p_, x_: fn(p_, bias, x_, tokens, target), p, x)
                return vjp((gy, jnp.asarray(gl, jnp.float32)
                            * jnp.ones_like(loss),
                            jnp.zeros_like(nb) if nb is not None else None))
            self._jits[fn] = jax.jit(fn), jax.jit(bwd)
        return self._jits[fn]

    @staticmethod
    def _weights(params, own, borrowed):
        return {part: params[layer]
                for part, layer in {**own, **borrowed}.items()}

    def forward(self, params, biases, tokens, label):
        """-> (losses by stage, every stage's input, new biases)."""
        x, xs, losses, new_biases = np.zeros((), np.float32), [], {}, {}
        with jax.default_matmul_precision("highest"):
            for i, (name, own, borrowed, fn, shift, weight) in enumerate(
                    self.stages):
                xs.append(x)
                moe = _moe_layer(own)
                y, loss, nb = self._fns(i)[0](
                    self._weights(params, own, borrowed),
                    biases[moe] if moe else None, x, tokens,
                    targets(label, shift))
                x = np.asarray(y)
                losses[name] = weight * float(loss)
                if moe:
                    new_biases[moe] = np.asarray(nb)
        return losses, xs, new_biases

    def backward(self, params, biases, tokens, label, xs):
        """Gradients by layer name (host), the borrowed layers' added to
        their owners'."""
        grads, lent = {}, {}
        # the chain's last output feeds nothing
        gy = np.zeros(xs[-1].shape, np.float32)
        with jax.default_matmul_precision("highest"):
            for i in reversed(range(len(self.stages))):
                name, own, borrowed, fn, shift, weight = self.stages[i]
                moe = _moe_layer(own)
                gp, gx = self._fns(i)[1](
                    self._weights(params, own, borrowed),
                    biases[moe] if moe else None, xs[i], tokens,
                    targets(label, shift), gy, weight)
                gp = jax.tree_util.tree_map(np.asarray, gp)
                gy = np.asarray(gx)
                for part, layer in borrowed.items():
                    lent[layer] = gp[part]
                for part, layer in own.items():
                    g = gp[part]
                    if layer in lent:
                        g = jax.tree_util.tree_map(np.add, g,
                                                   lent.pop(layer))
                    grads[layer] = g
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
    """``steps`` steps of Adam from ``params``: the losses by stage of
    each step, and the first step's gradients."""
    zeros = lambda t: jax.tree_util.tree_map(np.zeros_like, t)
    m1, m2 = zeros(params), zeros(params)
    all_losses, first_grads = [], None
    for t in range(1, steps + 1):
        losses, xs, new_biases = model.forward(params, biases, tokens, label)
        all_losses.append(losses)
        if t == steps:
            break
        grads = model.backward(params, biases, tokens, label, xs)
        if first_grads is None:
            first_grads = grads
        params, m1, m2 = adam_step(params, grads, m1, m2, t, lr)
        biases = new_biases
    return all_losses, first_grads


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
    """Which line of ``compared`` a leaf's gradient norm belongs to."""
    layer, rest = key.split("/", 1)
    if rest.startswith("router"):
        return "routers"
    if layer == "tok_embed":
        return "embed"
    if layer == "lm_head":
        return "head"
    if layer == "mtp_eh":
        return "w_eh"
    if layer in ("final_norm",):
        return "head"
    return layer.split("_")[0]          # b0 .. b4, mtp


# -- the contract -----------------------------------------------------------


def _ids(a):
    a = np.asarray(a)
    return a.reshape(a.shape[0], -1).astype(np.int32)


def _host(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), tree)


def _moe_names(c):
    names = [f"b{i}_moe" for i in range(c["first_k_dense_replace"],
                                        c["num_hidden_layers"])]
    return names + (["mtp_moe"] if c["num_nextn_predict_layers"] else [])


def initial_params(tr, seed):
    """The weights the trainer started from, on the host: the program's
    initialiser under the conf's seed, run once more (the step donated
    the arrays themselves; jitted as the trainer's own call is, so the
    executable is the one it built)."""
    return _host(jax.jit(tr.net.init)(jax.random.PRNGKey(seed))[0])


def timed_step(tr, batch):
    """One more step of the timed path's own ``update`` on ``batch`` (the
    class's method: a harness that wrapped the instance's counts its own
    steps) -> ``(the step's loss, each head's own loss, the l2 norm of
    the step's gradient by layer and leaf)``. Adam's first moment is ``m1
    <- m1 + d1 (g - m1)``, so the step's gradient is what it did to
    ``m1``, leaf by leaf — from the executable the window runs, at no
    second compile and with no copy of the weights on the device. The
    heads' losses are the step's own train metric (``seq_logloss`` on
    each head's node, in the conf's order), reported as at a round's end
    before the step and after it: the window's round starts clean."""
    before = _host(tr.opt_state["m1"])
    tr.train_metric_report()
    type(tr).update(tr, batch)
    loss = float(tr.last_loss)
    heads = [float(v) for v in re.findall(r"seq_logloss:(\S+)",
                                          tr.train_metric_report())]

    def norm(after, b):            # one leaf on the host at a time
        g = (np.asarray(after, np.float64) - (1.0 - ADAM_D1) * b) / ADAM_D1
        return float(np.sqrt(np.sum(np.square(g))))
    return loss, heads, grad_norms(jax.tree_util.tree_map(
        norm, tr.opt_state["m1"], before))


def check(kind: str, view: dict):
    if kind != "train_steps":
        raise ValueError(f"references/joyai_llm_flash.py has no check "
                         f"{kind!r}")
    c, tr = view["config"], view["trainer"]
    lim = LIMITS[view["dtype"]]
    batch = view["batch0"]
    label = _ids(batch.label if batch.host_label is None
                 else batch.host_label)
    tokens = _ids(batch.data)
    lr = float(dict(view["defaults"]).get("eta", 0.01))
    off = 1.01 if VARIANT == "weights_off" else 1.0
    scale = lambda t: jax.tree_util.tree_map(lambda a: a * np.float32(off), t)
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

    # 1. three steps of Adam from the initial weights against the losses
    #    the timed path's first three steps gave
    seed = int(dict(view["defaults"]).get("seed", 0))
    zero_bias = {n: np.zeros((c["n_routed_experts_published"],), np.float32)
                 for n in _moe_names(c)}
    params0 = scale(initial_params(tr, seed))
    mark("initial_weights")
    losses, _ = train_steps(model, params0, zero_bias, tokens, label, lr)
    del params0
    mark("three_steps")
    for t, by_stage in enumerate(losses):
        want = sum(by_stage.values())
        got = view["warm_losses"][t]
        said[f"loss_step{t + 1}_program"] = got
        said[f"loss_step{t + 1}_reference"] = want
        hold(f"loss_step{t + 1}_abs_diff", abs(got - want), lim["loss_abs"])
    # 2. one more step of the timed path, at the weights as the warm-up
    #    left them: its loss and every leaf's gradient norm against the
    #    reference's forward and backward at the same weights
    #    — under a PLANTED selection bias (a tenth of a standard normal,
    #    seeded; the warm-up's own is a few thousandths), so that the
    #    choice with the bias and the gates without it are both at stake;
    #    the routers get their own bias back afterwards
    rng = np.random.RandomState(seed)
    biases = {n: (PLANTED_BIAS * rng.standard_normal(
        c["n_routed_experts_published"])).astype(np.float32)
        for n in _moe_names(c)}
    now = scale({name: _host(leaves) for name, leaves in tr.params.items()})
    own_bias = {n: tr.net_state[n]["sel_bias"] for n in biases}
    for n, b in biases.items():
        tr.net_state[n]["sel_bias"] = jax.device_put(
            b, own_bias[n].sharding)
    p_loss, p_heads, got = timed_step(tr, batch)
    for n, b in own_bias.items():
        tr.net_state[n]["sel_bias"] = b
    mark("program_probe")
    r_losses, xs, _ = model.forward(now, biases, tokens, label)
    grads = model.backward(now, biases, tokens, label, xs)
    del now, xs
    mark("reference_probe")
    view["say"](reference_check_seconds=seconds)
    weights = {name: weight for name, _, _, _, _, weight in model.stages}
    for n, stage in enumerate(k for k in ("head", "mtp_head")
                              if k in r_losses):
        head = "main" if stage == "head" else "mtp"
        got_h = p_heads[n] if n < len(p_heads) else float("nan")
        said[f"probe_loss_{head}_program"] = got_h
        said[f"probe_loss_{head}_reference"] = \
            r_losses[stage] / weights[stage]
        hold(f"probe_loss_{head}_abs_diff",
             abs(got_h - r_losses[stage] / weights[stage]),
             lim["probe_loss_abs"])
    said["probe_loss_program"] = p_loss
    said["probe_loss_reference"] = sum(r_losses.values())
    hold("probe_loss_abs_diff", abs(p_loss - sum(r_losses.values())),
         lim["probe_loss_abs"])
    want = grad_norms(grads)
    worst = {}
    for key, ref in want.items():
        rel = abs(got[key] - ref) / max(ref, 1e-30)
        g = group_of(key)
        if rel >= worst.get(g, (-1.0, ""))[0]:
            worst[g] = (rel, key)
    for g, (rel, key) in sorted(worst.items()):
        said[f"grad_norm_{g}_worst_leaf"] = key
        hold(f"grad_norm_{g}_rel_diff", rel, lim[
            "grad_norm_rel_routers" if g == "routers" else "grad_norm_rel"])
    # 3. no pair of a held expert was left out
    dropped = float(sum(np.asarray(tr.net_state[n]["stats"])[2]
                        for n in _moe_names(c)))
    hold("moe_pairs_dropped", dropped, 0.0)
    return ok, said


# -- the operation count ------------------------------------------------------


def attention_flops(c, positions: int) -> float:
    """The causal products of ONE attention layer on one row, forward:
    q.k over ``d_nope + d_rope`` and p.v over ``v_head_dim``, two
    operations a multiply-add, over the S (S + 1) / 2 pairs a causal
    layer attends."""
    pairs = positions * (positions + 1) / 2
    return 2.0 * pairs * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def expert_pair_flops(c) -> float:
    """One (position, expert) pair through one routed expert, forward."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def matrix_params_per_position(c) -> float:
    """Parameters of the matrix products one position passes through,
    forward, the held experts' by the EXPECTED pairs a position (``topk x
    held / all``) so that the count does not move with the routing. The
    embedding is a gather and the norms are bandwidth: neither counts."""
    E, H = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    attn = E * rq + rq * H * (dn + dr) + E * (rkv + dr) \
        + rkv * H * (dn + dv) + H * dv * E
    dense = 3 * E * c["intermediate_size"]
    pairs = c["num_experts_per_tok"] * c["n_routed_experts"] \
        / c["n_routed_experts_published"]
    moe = E * c["n_routed_experts_published"] \
        + 3 * E * c["moe_intermediate_size"] * (c["n_shared_experts"] + pairs)
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    total = n_dense * (attn + dense) + n_moe * (attn + moe) \
        + E * c["vocab_size"]
    if c["num_nextn_predict_layers"]:
        total += 2 * E * E + attn + moe + E * c["vocab_size"]
    return float(total)


def attention_layers(c) -> int:
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def step_flops(c, rows: int, positions: int) -> float:
    """Forward, and a backward of two products per forward product:
    6 x parameters a position passes through, plus the causal attention
    products forward once and backward twice."""
    return rows * (6.0 * positions * matrix_params_per_position(c)
                   + 3.0 * attention_layers(c) * attention_flops(c, positions))


def train_step_flops(view: dict) -> float:
    positions = int(view["config"]["input_shape"][-1])
    return step_flops(view["config"], view["rows"], positions)
