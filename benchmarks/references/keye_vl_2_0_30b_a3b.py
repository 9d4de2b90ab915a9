"""Reference module ``keye_vl_2_0_30b_a3b``: the plain float32 reference,
``correct`` and operation count of Keye-VL-2.0-30B-A3B's language model
(Kwai-Keye, ``model_type`` ``KeyeVL2``: grouped-query attention over the
keys a learned indexer picks for each query — DeepSeek sparse attention,
arXiv:2512.02556 section 2.1, ``sa_config`` — an RMS norm on each head's q
and k, a rotary whose pairs read three position rows by
``mrope_section``, and 128 softmax-routed experts of which 8 a token,
without a shared one) as one chip of a group of 16 trains it, as a
configuration brings them to ``benchmarks/run.py`` (its header has the
contract). The vision tower is not here and nothing stands in for it:
the positions are text.

Written from the keys of the model's published ``config.json``, which the
configuration file repeats, in plain ``jax.numpy``: no layer class, no
attention kernel, no grouped product, no mixed precision, nothing
imported from the program or from another configuration's reference.
From the program it takes what a checkpoint reader would — the weights
by layer name, in the program's layouts (the names are those
``tools/gen_joyai_conf.py`` writes) — and, through the ``trainer`` handle
of the view, what a checkpoint holds beside them: the initial weights,
Adam's first moment around one more step of the timed path's own
``update``, that step's train metric and the layers' own counters; and
two of the program's functions by the layer object it built, each run on
the REFERENCE's input: its selection and its rotary.

With ``x`` a position's vector, ``RMS(v) = v / sqrt(mean(v^2) + eps) * g``,
no bias, all layers alike:

* block: ``x + attn(RMS(x))`` then ``+ experts(RMS(.))``; the stack's
  output through a final RMS norm and the untied head; mean token
  cross-entropy.
* ``attn``, ``xh`` the normed input, ``H`` query heads, ``H_kv``
  key/value heads, ``d = head_dim``: ``q = xh W_q``, ``k = xh W_k``, ``v
  = xh W_v``; each head's q and k through an RMS norm over its ``d``
  features, one gain vector for q and one for k; rotary on q and k; query
  head ``h`` reads key/value head ``h // (H / H_kv)``; ``score_h[t,s] =
  q.k / sqrt(d)``.
* the indexer (``sa_config``: ``J = indexer_num_heads`` heads of ``di =
  indexer_head_dim``, one key head, ``topk``), on ``xd =
  stop_gradient(xh)``: ``qI = xd W_qI``, ``kI = LayerNorm(xd W_kI)`` (gain
  and bias), both rotated over all ``di`` features at ``rope_theta`` by
  the temporal position; ``w = xd W_w / sqrt(J di)``; ``I[t,s] = sum_j
  w[t,j] relu(qI[t,j] . kI[s])``. ``S_t`` = the ``topk`` keys ``s <= t``
  of largest ``I[t,s]`` (every one while ``t < topk``; a tie to the lower
  ``s``): ``jax.lax.top_k`` on the masked row.
* ``a_h[t,.] = softmax of score_h[t,s] over s in S_t``; ``o_h = sum_s a_h
  v``; ``y = concat_h(o_h) W_o``: an explicit masked softmax a head at a
  time.
* the indexer's loss, a layer: ``p[t,s] = stop_gradient(sum_h a_h[t,s] /
  H)``; ``L_I = mean_t sum_{s in S_t} p (log p - log softmax_{S_t}(I[t,.]))``.
  The objective is the cross-entropy plus ``index_loss_coef`` times the
  layers' sum. The indexer's leaves get their gradient from ``L_I`` alone
  and nothing else gets any from it.
* rotary, on halves (``rotate_half``), ``rope_scaling.mrope_section`` =
  three counts of pairs: pair ``i`` of the head's ``d / 2`` turns by
  ``pos[c(i)] theta^(-2i/d)``, ``c(i)`` the section ``i`` lies in
  (contiguous): temporal, height, width. Text: the three rows are the
  token's index.
* experts: ``p = softmax(x W_r)`` over all ``num_experts_published``;
  chosen = top ``num_experts_per_tok``; ``g_i = p_i / sum of the chosen
  p``; ``y = sum over the chosen experts THIS CHIP HOLDS of g_i E_i(x)``,
  ``E_i`` SwiGLU — every held expert densely over all positions under its
  gate.

Every product runs under ``jax.default_matmul_precision("highest")``. At
full width beside a trainer that holds 8.8 GB the reference computes
STAGE BY STAGE — one stage's weights on the device at a time, every
stage's input kept on the host, the backward by ``vjp`` a stage — the
eight layers one stage's executable eight times, attention one head at a
time and the indexer one of its heads at a time.

``VARIANT`` names a planted fault (the variant modules under
``tests/benchmarks/data/keye_controls/`` set it): every control has to
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
#: ``select_off`` every causal key attended; ``topk_half`` half the keys
#: a query; ``no_relu`` the relu left out of the indexer's score;
#: ``no_w`` the indexer's weights left out (1); ``no_index_loss`` the
#: indexer's loss left out; ``index_attached`` the indexer's input not
#: detached; ``p_attached`` the indexer's target not detached;
#: ``no_qk_norm`` the heads' q/k norm left out; ``kv_mod`` query head h
#: reading key/value head ``h % H_kv``; ``sections_permuted`` the rotary's
#: sections in the order width, temporal, height; ``top7`` one expert
#: fewer a token; ``no_renorm`` the chosen probabilities not renormalised
VARIANT = None

#: Adam as ``cxxnet_tpu/optim.py`` has it (reference adam_updater)
ADAM_D1, ADAM_D2, ADAM_EPS = 0.1, 0.001, 1e-8

#: the limits of ``check("train_steps")``, by the program's compute
#: dtype; each stands between the largest reading of sound runs and the
#: smallest of the control it is there to catch, with room on both
#: sides. bfloat16 — the cell, on a TPU v5e (my chip runs, PR 34; PERF.md
#: section 6 has every reading): sound seeds through ``run.py`` and
#: ``keye_controls/readings.py``, the float8 control on one of them:
#:   a step's objective, |program - reference|: sound 2.3e-5 ... 1.8e-3;
#:   float8 1.5e-2 / 0.10 / 0.10 on steps 1 / 2 / 3;
#:   the seventh step's objective and the metric's cross-entropy: sound
#:   6.9e-6 ... 3.8e-4; float8's cross-entropy 2.2e-2 (its objective
#:   4.1e-4: the two parts' errors cancel, the metric's limit holds it);
#:   the seventh step's L_I, relative: sound 3e-5 ... 1.4e-3; float8
#:   6.8e-2;
#:   a leaf's gradient norm, relative (``GRAD_FLOOR``), worst leaf of a
#:   group: blocks, embedding 5.0e-4 ... 2.5e-2 (an attention layer's k
#:   and its norm's gain the largest); float8 0.58 ... 0.93; head 2.0e-4
#:   ... 8.7e-4, float8 4.8e-2; routers 1.9e-2 ... 5.3e-2, float8 0.83;
#:   the indexer's leaves 4.1e-3 ... 1.6e-2, float8 0.33;
#:   ``select_share_min``: the share of the program's selected pairs that
#:   lie in the reference's float32 set: sound 0.99799 ... 0.99802 on
#:   every seed (bf16 products move a score by about 2^-9 of its size and
#:   swap only keys within that of the row's 2048th), float8 0.99034;
#:   ``rotary_abs``: the program's rotary at grid positions against the
#:   reference's on unit-size inputs: 1.2e-2 ... 1.5e-2 (a bf16 rounding
#:   of values up to 4), the permuted sections' control 5.
#: float32 — the tests' toy size on the sandbox's CPU: sound under 3e-6
#: (losses) and 5e-7 (gradient norms), the selection the reference's own
#: set; every control over a limit by at least one number.
LIMITS = {
    "float32": {"loss_abs": 5e-5, "probe_loss_abs": 5e-5,
                "index_loss_rel": 2e-3, "grad_norm_rel": 1e-3,
                "grad_norm_rel_routers": 1e-3,
                "grad_norm_rel_indexer": 2e-3, "grad_norm_rel_head": 1e-3,
                "select_share_min": 0.98, "rotary_abs": 1e-4},
    "bfloat16": {"loss_abs": 5e-3, "probe_loss_abs": 1e-3,
                 "index_loss_rel": 1e-2, "grad_norm_rel": 0.1,
                 "grad_norm_rel_routers": 0.15,
                 "grad_norm_rel_indexer": 0.07, "grad_norm_rel_head": 1e-2,
                 "select_share_min": 0.995, "rotary_abs": 0.05},
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


def layer_norm(x, gamma, beta, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gamma + beta


def text_positions(rows: int, positions: int):
    """The three rows of a text token: its index, thrice. (rows, 3, S)."""
    return np.broadcast_to(np.arange(positions, dtype=np.float64),
                           (rows, 3, positions))


def rotary(x, theta: float, pos, sections):
    """(B, S, H, d) rotated whole, on halves: feature ``i`` pairs with ``i
    + d/2`` and turns by ``pos[:, c(i)] theta^(-2i/d)``; ``pos`` (B, 3, S)
    float64 on the host, ``sections`` the pairs read from each row, in
    order."""
    half = x.shape[-1] // 2
    rows = np.repeat(np.arange(len(sections)), sections)
    if VARIANT == "sections_permuted" and len(sections) == 3:
        rows = np.repeat(np.array([2, 0, 1]), sections)
    inv = theta ** (-2.0 * np.arange(half, dtype=np.float64) / (2 * half))
    ang = np.asarray(pos, np.float64)[:, rows, :].transpose(0, 2, 1) * inv
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def index_scores(p, xd, c, pos):
    """``I`` (B, S, S) float32 from the indexer's input, one of its heads
    at a time."""
    sa = c["sa_config"]
    J, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta = float(c["rope_theta"])
    temporal = np.asarray(pos)[:, :1]
    qi = rotary(mm("bse,ejd->bsjd", xd, p["iq"]["wmat"]), theta, temporal,
                [di // 2])
    ki = layer_norm(mm("bse,ed->bsd", xd, p["ik"]["wmat"]),
                    p["iknorm"]["gamma"], p["iknorm"]["beta"],
                    c["rms_norm_eps"])
    ki = rotary(ki[:, :, None, :], theta, temporal, [di // 2])[:, :, 0]
    w = mm("bse,ej->bsj", xd, p["iw"]["wmat"]) * (J * di) ** -0.5
    if VARIANT == "no_w":
        w = jnp.ones_like(w)

    @jax.checkpoint
    def one(a):
        q_j, w_j = a
        s = mm("btd,bsd->bts", q_j, ki)
        if VARIANT != "no_relu":
            s = jax.nn.relu(s)
        return w_j[..., None] * s
    total, _ = jax.lax.scan(
        lambda acc, a: (acc + one(a), None),
        jnp.zeros(xd.shape[:2] + (xd.shape[1],), jnp.float32),
        (jnp.moveaxis(qi, 2, 0), jnp.moveaxis(w, 2, 0)))
    return total


def selection(scores, topk: int):
    """``S_t`` as a boolean (B, S, S): ``jax.lax.top_k`` on the row
    masked to its causal keys."""
    B, S, _ = scores.shape
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    if VARIANT == "select_off":
        return jnp.broadcast_to(causal, scores.shape)
    if VARIANT == "topk_half":
        topk = topk // 2
    k = min(topk, S)
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
    hit = jnp.zeros((B, S, S), jnp.bool_).at[
        jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None],
        idx].set(True)
    return hit & causal


def attention(p, x, c, pos):
    """-> ``(y, L_I)`` on the normed input (B, S, E), one query head at a
    time."""
    S, d, eps = x.shape[1], c["head_dim"], c["rms_norm_eps"]
    H, Hkv = p["q"]["wmat"].shape[1], p["k"]["wmat"].shape[1]
    theta = float(c["rope_theta"])
    sections = c["rope_scaling"]["mrope_section"]
    norm = (lambda a, g: a) if VARIANT == "no_qk_norm" \
        else (lambda a, g: rms(a, g, eps))
    k = rotary(norm(mm("bse,ehd->bshd", x, p["k"]["wmat"]),
                    p["knorm"]["gamma"]), theta, pos, sections)
    v = mm("bse,ehd->bshd", x, p["v"]["wmat"])
    xd = x if VARIANT == "index_attached" else jax.lax.stop_gradient(x)
    scores = index_scores(p, xd, c, pos)
    keep = selection(jax.lax.stop_gradient(scores), c["sa_config"]["topk"])
    reads = np.arange(H) % Hkv if VARIANT == "kv_mod" \
        else np.arange(H) // (H // Hkv)

    @jax.checkpoint
    def head(w_q, w_o, kv):
        q = mm("bse,ed->bsd", x, w_q)[:, :, None, :]
        q = rotary(norm(q, p["qnorm"]["gamma"]), theta, pos,
                   sections)[:, :, 0]
        s = mm("bqd,bkd->bqk", q, k[:, :, kv]) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        o = mm("bqk,bkd->bqd", pr, v[:, :, kv])
        return mm("bsd,de->bse", o, w_o), pr

    def step(acc, a):
        y, pr = head(*a)
        return (acc[0] + y, acc[1] + pr), None
    (total, psum), _ = jax.lax.scan(
        step, (jnp.zeros_like(x), jnp.zeros(keep.shape, jnp.float32)),
        (jnp.moveaxis(p["q"]["wmat"], 1, 0), p["o"]["wmat"],
         jnp.asarray(reads, jnp.int32)))
    if VARIANT == "no_index_loss":
        return total, jnp.zeros((), jnp.float32)
    target = psum / H
    if VARIANT != "p_attached":
        target = jax.lax.stop_gradient(target)
    logq = scores - jax.nn.logsumexp(
        jnp.where(keep, scores, -jnp.inf), axis=-1, keepdims=True)
    some = keep & (target > 0)
    kl = jnp.where(some, target * (jnp.log(jnp.where(some, target, 1.0))
                                   - logq), 0.0)
    return total, jnp.mean(jnp.sum(kl, axis=-1))


def route(p, x, c):
    """Gates ``(N, X)`` — zero where an expert was not chosen — for
    positions ``x`` (N, E)."""
    k = c["num_experts_per_tok"] - (1 if VARIANT == "top7" else 0)
    logits = jnp.einsum("ne,ex->nx", x, p["router"]["wmat"],
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(logits, axis=-1)
    kth = jnp.sort(s, axis=1)[:, -k][:, None]
    gates = jnp.where(s >= kth, s, 0.0)
    if c["norm_topk_prob"] and VARIANT != "no_renorm":
        gates = gates / jnp.sum(gates, axis=1, keepdims=True)
    return gates


def experts(p, x, c):
    """The expert layer's partial sum on (B, S, E)."""
    B, S, E = x.shape
    xf = x.reshape(B * S, E)
    gates = route(p, xf, c)
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
    return out.reshape(B, S, E)


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
    """``[(name, {part: layer}, fn)]``: the model as a chain. ``fn(p, x,
    tokens, target) -> (y, loss)`` with ``p`` the weights by PART, so
    that the layers, which differ in nothing but their weights, are two
    functions and compile once each. An attention half's loss is its
    ``L_I``, unweighted."""
    eps, out = c["rms_norm_eps"], []

    def embed(p, x, tokens, target):
        return p["embed"]["wmat"][tokens], 0.0

    def attn_half(p, x, tokens, target):
        y, loss = attention(p["attn"], rms(x, p["ln1"]["gamma"], eps), c,
                            text_positions(*x.shape[:2]))
        return x + y, loss

    def expert_half(p, x, tokens, target):
        return x + experts(p["moe"], rms(x, p["ln2"]["gamma"], eps), c), 0.0

    def head(p, x, tokens, target):
        h = rms(x, p["norm"]["gamma"], eps)
        return h, head_loss(h, p["head"]["wmat"], target)

    out.append(("embed", {"embed": "tok_embed"}, embed))
    for i in range(c["num_hidden_layers"]):
        pre = f"b{i}"
        out.append((pre + "_attn", {"ln1": pre + "_ln1",
                                    "attn": pre + "_attn"}, attn_half))
        out.append((pre + "_mlp", {"ln2": pre + "_ln2",
                                   "moe": pre + "_moe"}, expert_half))
    out.append(("head", {"norm": "final_norm", "head": "lm_head"}, head))
    return out


class Model:
    """The stages' functions compiled once each way, and the
    stage-by-stage walk: weights, Adam's moments and every stage's input
    live on the host (``numpy``), one stage's on the device while it
    runs. A stage's loss counts ``coef`` times in the objective where it
    is an indexer's, once where it is the head's."""

    def __init__(self, c):
        self.c = c
        self.coef = float(c.get("index_loss_coef", 1.0))
        self.stages = stages(c)
        self._jits = {}

    def _fns(self, i):
        fn = self.stages[i][2]
        if fn not in self._jits:
            def bwd(p, x, tokens, target, gy, gl):
                (y, loss), vjp = jax.vjp(
                    lambda p_, x_: fn(p_, x_, tokens, target), p, x)
                return vjp((gy, gl * jnp.ones_like(loss)))
            self._jits[fn] = jax.jit(fn), jax.jit(bwd)
        return self._jits[fn]

    @staticmethod
    def _weights(params, own):
        return {part: params[layer] for part, layer in own.items()}

    def _weight_of_loss(self, i):
        return self.coef if self.stages[i][0].endswith("_attn") else 1.0

    def forward(self, params, tokens, label):
        """-> (the cross-entropy, the layers' summed L_I, every stage's
        input)."""
        x, xs, ce, index = np.zeros((), np.float32), [], 0.0, 0.0
        with jax.default_matmul_precision("highest"):
            for i, (name, own, fn) in enumerate(self.stages):
                xs.append(x)
                y, part = self._fns(i)[0](self._weights(params, own), x,
                                          tokens, label)
                x = np.asarray(y)
                if name.endswith("_attn"):
                    index += float(part)
                else:
                    ce += float(part)
        return ce, index, xs

    def backward(self, params, tokens, label, xs):
        """Gradients of ``ce + coef * index`` by layer name (host)."""
        grads = {}
        gy = np.zeros(xs[-1].shape, np.float32)
        with jax.default_matmul_precision("highest"):
            for i in reversed(range(len(self.stages))):
                name, own, fn = self.stages[i]
                gp, gx = self._fns(i)[1](
                    self._weights(params, own), xs[i], tokens, label, gy,
                    np.float32(self._weight_of_loss(i)))
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


def train_steps(model, params, tokens, label, lr, steps=3):
    """``[(cross-entropy, summed L_I)]`` of ``steps`` steps of Adam from
    ``params``."""
    zeros = lambda t: jax.tree_util.tree_map(np.zeros_like, t)
    m1, m2 = zeros(params), zeros(params)
    losses = []
    for t in range(1, steps + 1):
        ce, index, xs = model.forward(params, tokens, label)
        losses.append((ce, index))
        if t == steps:
            break
        grads = model.backward(params, tokens, label, xs)
        params, m1, m2 = adam_step(params, grads, m1, m2, t, lr)
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


#: the indexer's leaves of an attention layer, by the program's names
INDEXER = ("iq", "ik", "iknorm", "iw")


#: a leaf whose reference gradient is smaller than this share of the
#: largest among the leaves of its KIND over the stack (``b3_moe/g/wmat``
#: and ``b6_moe/g/wmat`` are one kind) has its difference held to that
#: share, not to its own norm. The expert path's leaves — the held
#: experts, the router, the norm before them — get their gradient from
#: the held pairs alone, and on one repeated batch, with neither a bias
#: nor a balance loss, a layer's held experts lose nearly every position
#: within the warm-up's six steps on most seeds (my chip runs, PR 34: 0,
#: 4, 25, 57 of 65 536 pairs in four layers of one seed at the probe,
#: beside 21 792 in another; PERF.md section 6). Such a leaf's gradient is
#: the sum over a handful of pairs at the router's top-8 margin, of which
#: bf16 and float32 keep different ones (3 against 4, 0 against none):
#: relative to ITSELF the difference reads 0.1, 0.5 or, against a
#: reference of exactly zero, 1e20, on sound runs; relative to a quarter
#: of a loaded layer's it reads under 0.03 on every seed, and the float8
#: control still reads 0.6 and more in every group (the floor only ever
#: applies to a leaf under a quarter of its kind's largest)
GRAD_FLOOR = 0.25


def kind_of(key):
    """A leaf's name with its block's index taken out."""
    return re.sub(r"^b\d+_", "", key)


def group_of(key):
    """Which line of ``compared`` a leaf's gradient norm belongs to."""
    layer, rest = key.split("/", 1)
    if rest.startswith("router"):
        return "routers"
    if rest.split("/", 1)[0] in INDEXER:
        return "indexer"
    if layer == "tok_embed":
        return "embed"
    if layer in ("lm_head", "final_norm"):
        return "head"
    return layer.split("_")[0]          # b0 .. b7


# -- the contract -----------------------------------------------------------


def _ids(a):
    a = np.asarray(a)
    return a.reshape(a.shape[0], -1).astype(np.int32)


def _host(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), tree)


def _names(c, what):
    return [f"b{i}_{what}" for i in range(c["num_hidden_layers"])]


def selected_pairs(topk: int, positions: int) -> int:
    """``sum_t min(t + 1, topk)``: the pairs a row's selection keeps."""
    k = min(topk, positions)
    return k * (k + 1) // 2 + (positions - k) * k


def initial_params(tr, seed):
    """The weights the trainer started from, on the host: the program's
    initialiser under the conf's seed, run once more."""
    return _host(jax.jit(tr.net.init)(jax.random.PRNGKey(seed))[0])


def timed_step(tr, batch, c):
    """One more step of the timed path's own ``update`` on ``batch`` ->
    ``(the step's objective, its train metric's cross-entropy, the
    layers' summed L_I by their own counters, the l2 norm of the step's
    gradient by layer and leaf)``. Adam's first moment is ``m1 <- m1 + d1
    (g - m1)``, so the step's gradient is what it did to ``m1``."""
    before = _host(tr.opt_state["m1"])
    tr.train_metric_report()
    type(tr).update(tr, batch)
    loss = float(tr.last_loss)
    said = [float(v) for v in re.findall(r"seq_logloss:(\S+)",
                                         tr.train_metric_report())]
    index = float(sum(np.asarray(tr.net_state[n]["dsa_stats"])[1]
                      for n in _names(c, "attn")))

    def norm(after, b):            # one leaf on the host at a time
        g = (np.asarray(after, np.float64) - (1.0 - ADAM_D1) * b) / ADAM_D1
        return float(np.sqrt(np.sum(np.square(g))))
    return loss, said[0] if said else float("nan"), index, grad_norms(
        jax.tree_util.tree_map(norm, tr.opt_state["m1"], before))


def _layer_named(tr, name):
    return [layer for layer in tr.net.layers if layer.name == name][0]


def check(kind: str, view: dict):
    if kind != "train_steps":
        raise ValueError("references/keye_vl_2_0_30b_a3b.py has no check "
                         f"{kind!r}")
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

    # 1. three steps of Adam from the initial weights against the
    #    objectives the timed path's first three steps gave (the harness
    #    keeps a step's objective whole; the parts are apart at 2.)
    seed = int(dict(view["defaults"]).get("seed", 0))
    params0 = initial_params(tr, seed)
    mark("initial_weights")
    losses = train_steps(model, params0, tokens, label, lr)
    del params0
    mark("three_steps")
    for t, (ce, index) in enumerate(losses):
        got = view["warm_losses"][t]
        said[f"loss_step{t + 1}_program"] = got
        said[f"loss_step{t + 1}_reference_cross_entropy"] = ce
        said[f"loss_step{t + 1}_reference_index"] = index
        hold(f"loss_step{t + 1}_abs_diff",
             abs(got - (ce + model.coef * index)), lim["loss_abs"])
    # 2. one more step of the timed path, at the weights as the warm-up
    #    left them: both losses and every leaf's gradient norm against
    #    the reference's forward and backward at the same weights
    now = {name: _host(leaves) for name, leaves in tr.params.items()}
    p_loss, p_metric, p_index, got = timed_step(tr, batch, c)
    mark("program_probe")
    r_ce, r_index, xs = model.forward(now, tokens, label)
    grads = model.backward(now, tokens, label, xs)
    x1 = xs[1]
    del xs
    mark("reference_probe")
    said["probe_loss_program"] = p_loss
    said["probe_loss_metric_program"] = p_metric
    said["probe_index_loss_program"] = p_index
    said["probe_loss_reference"] = r_ce
    said["probe_index_loss_reference"] = r_index
    hold("probe_loss_abs_diff",
         abs(p_loss - (r_ce + model.coef * r_index)), lim["probe_loss_abs"])
    hold("probe_loss_metric_abs_diff", abs(p_metric - r_ce),
         lim["probe_loss_abs"])
    hold("probe_index_loss_rel_diff",
         abs(p_index - r_index) / max(abs(r_index), 1e-30),
         lim["index_loss_rel"])
    want = grad_norms(grads)
    del grads
    # a leaf's difference is held to its own norm, or to GRAD_FLOOR of
    # the largest norm among the leaves of its kind over the stack where
    # its own is smaller (GRAD_FLOOR's note says why)
    largest = {}
    for key, ref in want.items():
        largest[kind_of(key)] = max(largest.get(kind_of(key), 0.0), ref)
    worst, raw = {}, {}
    for key, ref in want.items():
        diff = abs(got[key] - ref)
        rel = diff / max(ref, GRAD_FLOOR * largest[kind_of(key)], 1e-30)
        if diff > 1e-2 * max(ref, 1e-30):
            raw[key] = [got[key], ref]
        g = group_of(key)
        if rel >= worst.get(g, (-1.0, ""))[0]:
            worst[g] = (rel, key)
    view["say"](grad_norms_program_reference_apart_by_over_a_hundredth=raw)
    for g, (rel, key) in sorted(worst.items()):
        said[f"grad_norm_{g}_worst_leaf"] = key
        hold(f"grad_norm_{g}_rel_diff", rel, lim.get(
            "grad_norm_rel_" + g, lim["grad_norm_rel"]))
    # 3. no pair of a held expert was left out, and every layer's own
    #    counter of selected pairs reads what the equations give
    dropped = float(sum(np.asarray(tr.net_state[n]["stats"])[2]
                        for n in _names(c, "moe")))
    hold("moe_pairs_dropped", dropped, 0.0)
    rows, positions = tokens.shape
    pairs = rows * selected_pairs(c["sa_config"]["topk"], positions)
    said["selected_pairs_expected"] = pairs
    hold("selected_pairs_worst_layer_abs_diff", float(max(
        abs(float(np.asarray(tr.net_state[n]["dsa_stats"])[0]) - pairs)
        for n in _names(c, "attn"))), 0.0)
    # 4. the selection itself: the program's own selection, by the layer
    #    it built, on the reference's first-layer input, against the
    #    reference's float32 set
    layer = _layer_named(tr, "b0_attn")
    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        xh = jax.jit(lambda x, g: rms(x, g, eps))(
            x1, now["b0_ln1"]["gamma"])
        theirs = np.asarray(jax.jit(
            lambda p, x: selection(index_scores(
                p, x, c, text_positions(*x.shape[:2])),
                c["sa_config"]["topk"]))(now["b0_attn"], xh))
    ours = np.asarray(jax.jit(layer.select)(
        now["b0_attn"], jnp.asarray(xh, view["dtype"]))) != 0
    mark("selection")
    said["select_pairs_program"] = int(ours.sum())
    said["select_pairs_reference"] = int(theirs.sum())
    hold("select_pairs_abs_diff", float(abs(int(ours.sum()) - pairs)), 0.0)
    share = float((ours & theirs).sum()) / max(int(ours.sum()), 1)
    said["select_share_in_reference"] = share
    hold("select_share_short_of_one_diff", 1.0 - share,
         1.0 - lim["select_share_min"])
    del ours, theirs
    # 5. the rotary at grid positions (the cell's are text): the
    #    program's, by the same layer, against the reference's
    rng = np.random.RandomState(seed)
    grid = np.stack([rng.randint(0, 64, (rows, 16)) for _ in range(3)], 1)
    probe = rng.standard_normal((rows, 16, 2, c["head_dim"])) \
        .astype(np.float32)
    want_rot = np.asarray(rotary(
        jnp.asarray(probe), float(c["rope_theta"]), grid,
        c["rope_scaling"]["mrope_section"]))
    got_rot = np.asarray(layer.rotate(
        jnp.asarray(probe, view["dtype"]), jnp.asarray(grid)), np.float32)
    hold("rotary_grid_max_abs_diff",
         float(np.max(np.abs(got_rot - want_rot))), lim["rotary_abs"])
    view["say"](reference_check_seconds=seconds)
    return ok, said


# -- the operation count ------------------------------------------------------


def causal_pairs(positions: int) -> float:
    return positions * (positions + 1) / 2


def attention_flops(c, positions: int) -> float:
    """The main attention's products of ALL layers on one row, forward:
    q.k and p.v over ``head_dim`` each, two operations a multiply-add,
    over the SELECTED pairs of every head — whatever the program
    executes: a kernel that multiplies pairs it then masks does more for
    the same count."""
    return 2.0 * selected_pairs(c["sa_config"]["topk"], positions) * 2 \
        * c["head_dim"] * c["num_attention_heads"] * c["num_hidden_layers"]


def index_flops(c, positions: int) -> float:
    """The indexer's score products of ALL layers on one row, forward:
    its heads' q.k over ``indexer_head_dim`` over the causal pairs."""
    sa = c["sa_config"]
    return 2.0 * causal_pairs(positions) * sa["indexer_num_heads"] \
        * sa["indexer_head_dim"] * c["num_hidden_layers"]


def expert_pair_flops(c) -> float:
    """One (position, expert) pair through one routed expert, forward."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def matrix_params_per_position(c) -> float:
    """Parameters of the matrix products one position passes through,
    forward, the held experts' by the EXPECTED pairs a position (``topk x
    held / all``) so that the count does not move with the routing. The
    embedding is a gather and the norms are bandwidth: neither counts."""
    E, d, sa = c["hidden_size"], c["head_dim"], c["sa_config"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    J, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    pairs = c["num_experts_per_tok"] * c["num_experts"] \
        / c["num_experts_published"]
    layer = 2 * E * H * d + 2 * E * Hkv * d \
        + E * (J * di + di + J) \
        + E * c["num_experts_published"] \
        + 3 * E * c["moe_intermediate_size"] * pairs
    return float(E * c["vocab_size"]) + c["num_hidden_layers"] * layer


def step_flops(c, rows: int, positions: int) -> float:
    """Forward, and a backward of two products per forward product:
    6 x parameters a position passes through, plus the main attention's
    products over the selected pairs and the indexer's over the causal
    pairs, forward once and backward twice."""
    return rows * (6.0 * positions * matrix_params_per_position(c)
                   + 3.0 * (attention_flops(c, positions)
                            + index_flops(c, positions)))


def train_step_flops(view: dict) -> float:
    positions = int(view["config"]["input_shape"][-1])
    return step_flops(view["config"], view["rows"], positions)
