"""Reference module ``laguna_s_2_1``: the plain float32 reference,
``correct`` and operation count of Laguna-S-2.1 (poolside, ``model_type``
``laguna``: grouped-query attention whose layers are full or 512-wide
windows by ``layer_types``, head counts that differ by layer, a sigmoid
gate a head, rotary by layer type — YaRN over half the head on full
layers, plain over the whole head on window layers — one leading dense
layer, then 256 softmax-routed experts of which 10 a token plus a shared
one) as one chip of a group of 32 trains it, as a configuration brings
them to ``benchmarks/run.py`` (its header has the contract).

Written from the keys of the model's published ``config.json``, which the
configuration file repeats, in plain ``jax.numpy``: no layer class, no
attention kernel, no grouped product, no mixed precision, nothing
imported from the program or from another configuration's reference.
From the program it takes what a checkpoint reader would — the weights
by layer name, in the program's layouts (the names are those
``tools/gen_joyai_conf.py`` writes) — and, through the ``trainer`` handle
of the view, what a checkpoint holds beside them: the initial weights
(the program's initialiser under its seed), Adam's first moment around
one more step of the timed path's own ``update`` — which is that step's
gradient, leaf by leaf — and that step's train metric.

With ``x`` a position's vector, ``RMS(v) = v / sqrt(mean(v^2) + eps) * g``,
no bias anywhere:

* attention of layer ``l``, ``H`` query heads (the file's
  ``num_attention_heads_per_layer[l]``: this chip's), ``H_kv`` key/value
  heads, ``d = head_dim``: ``q = x W_q``, ``k = x W_k``, ``v = x W_v``;
  rotary on q and k; query head ``h`` reads key/value head ``h // (H /
  H_kv)``; ``scores = q.k / sqrt(d)``; a ``full_attention`` layer is
  causal, a ``sliding_attention`` layer lets position ``i`` see ``j``
  with ``i - sliding_window < j <= i``; ``o_h = softmax(scores) v``;
  ``o_h <- sigmoid(x w_g[:, h]) o_h`` (``gating`` per-head: one scalar a
  head and position from the layer's normed input); ``y = concat_h(o_h)
  W_o``. Computed an explicit masked softmax a head at a time.
* rotary, on halves, by ``rope_parameters[layer type]``: the first ``d *
  partial_rotary_factor`` features are rotated at ``rope_theta``, the
  others pass through. ``rope_type`` ``yarn``: over the pairs ``i`` of
  the rotated part (``r`` features), ``f_i = theta^(-2i/r)``; ``dim(n) =
  r ln(original_max_position_embeddings / (2 pi n)) / (2 ln theta)``;
  ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``;
  ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = f_i
  (1 - ramp_i) + f_i / factor ramp_i``; cos and sin times
  ``attention_factor``.
* feed-forward: ``(silu(x W_g) * (x W_u)) W_d``.
* experts: ``p = softmax(x W_r)`` over all ``num_experts_published``
  experts (``num_experts`` is how many this chip holds, from
  ``expert_first`` on); chosen = top ``num_experts_per_tok`` by ``p``;
  ``g_i = p_i / sum of the chosen p * moe_routed_scaling_factor``; ``y =
  shared(x) + sum over the chosen experts THIS CHIP HOLDS of g_i
  E_i(x)`` — every held expert runs densely over all positions under its
  gate (zero where it was not chosen): the plainest form, and no
  relative of the program's sorted grouped products.
* block: ``x + attn(RMS(x))`` then ``+ ffn_or_experts(RMS(.))`` (dense
  where ``mlp_only_layers`` says so); the stack's output through a final
  RMS norm and the untied head; mean token cross-entropy.

Every product runs under ``jax.default_matmul_precision("highest")``.
At full width beside a trainer that holds 10.75 GB the reference computes
STAGE BY STAGE — one stage's weights on the device at a time, every
stage's input kept on the host, the backward by ``vjp`` a stage — and
attention one head at a time, the head's loss a slice of positions at a
time, so that it stays under 2 GB of the device.

``VARIANT`` names a planted fault (the variant modules under
``tests/benchmarks/data/laguna_controls/`` set it): every control has to
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
#: ``top9`` one expert fewer a token; ``sigmoid_scores`` sigmoid for
#: softmax in the router; ``no_renorm`` the chosen probabilities not
#: renormalised; ``no_scaling`` ``moe_routed_scaling_factor`` left out;
#: ``no_shared`` the shared expert left out; ``window_off`` window layers
#: attend causally; ``window_511`` a window one position short;
#: ``kv_mod`` query head h reading key/value head ``h % H_kv``;
#: ``no_gate`` the per-head gate left out; ``rope_full`` rotary over the
#: whole head on full layers; ``no_yarn`` YaRN's table left out (the
#: plain frequencies); ``no_attention_factor`` cos and sin unscaled
VARIANT = None

#: Adam as ``cxxnet_tpu/optim.py`` has it (reference adam_updater): decay
#: rates 0.1 / 0.001, the step ``lr sqrt(fix2) / fix1 * m1 / (sqrt(m2) +
#: 1e-8)``
ADAM_D1, ADAM_D2, ADAM_EPS = 0.1, 0.001, 1e-8

#: the limits of ``check("train_steps")``, by the program's compute
#: dtype; each stands between the largest reading of sound runs and the
#: smallest of the controls it is there to catch, with room on both
#: sides. bfloat16 — the cell, on a TPU v5e (my chip runs, PR 32; PERF.md
#: section 6 has every reading): fourteen seeds through ``run.py`` and
#: four controls on one of them:
#:   a step's loss, |program - reference|: sound 4.8e-6 ... 4.4e-3 (the
#:   third step's the largest); the float8 control 4.6e-3 / 0.112 / 0.226
#:   on steps 1 / 2 / 3, nine experts a token 1.5e-2 on the third, YaRN's
#:   table left out 2.0e-2 on the first;
#:   the seventh step's loss and the train metric's: sound 3.4e-5 ...
#:   4.8e-5; float8 8.6e-3, YaRN's table left out 6.3;
#:   a leaf's gradient norm, relative, worst leaf of a group: sound
#:   3.7e-3 ... 1.5e-2 over the blocks, embedding, head and gates
#:   (block 0 and the embedding the largest), 5.2e-3 ... 1.3e-2 over the
#:   routers; float8 0.445 ... 0.557, nine experts a token 4.8e-2 ...
#:   6.5e-2 over three expert blocks (2.6e-2 over the routers: under
#:   their limit, the blocks catch it), YaRN's table left out 0.99;
#:   a window of 511 reads as a sound run at this size (one position of
#:   512 in three layers' bands) and is held to the toy size only.
#: float32 — the tests' toy size on the sandbox's CPU: sound under 3e-6
#: (losses) and 3e-7 (gradient norms), every control over a limit by at
#: least one number.
LIMITS = {
    "float32": {"loss_abs": 5e-5, "probe_loss_abs": 5e-5,
                "grad_norm_rel": 1e-3, "grad_norm_rel_routers": 1e-3},
    "bfloat16": {"loss_abs": 1.2e-2, "probe_loss_abs": 1e-3,
                 "grad_norm_rel": 4e-2, "grad_norm_rel_routers": 4e-2},
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


def rotary_table(c, kind):
    """``(inv_freq over the rotated part's pairs, the factor on cos and
    sin)`` of a layer of type ``kind``, in float64."""
    r = c["rope_parameters"][kind]
    factor_rot = 1.0 if VARIANT == "rope_full" \
        else r["partial_rotary_factor"]
    rot = int(round(c["head_dim"] * factor_rot))
    theta = float(r["rope_theta"])
    i = np.arange(rot // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / rot)
    if r["rope_type"] != "yarn":
        return f, 1.0
    mscale = 1.0 if VARIANT == "no_attention_factor" \
        else float(r["attention_factor"])
    if VARIANT == "no_yarn":
        return f, mscale
    orig = r["original_max_position_embeddings"]

    def dim_of(n):
        return rot * math.log(orig / (2 * math.pi * n)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim_of(r["beta_fast"])), 0)
    high = min(math.ceil(dim_of(r["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + f / r["factor"] * ramp, mscale


def rotary(x, c, kind):
    """(B, S, H, d): the rotated part's feature ``i`` pairs with ``i +
    r/2`` (``rotate_half``), turned by ``pos * inv_freq_i``; the features
    past the rotated part pass through."""
    inv, mscale = rotary_table(c, kind)
    half = len(inv)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * mscale, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * mscale, jnp.float32)[None, :, None, :]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., 2 * half:]], axis=-1)


def attention(p, x, c, kind):
    """Grouped-query attention on (B, S, E), one query head at a time;
    the heads are the weights' own (this chip's share)."""
    S, d = x.shape[1], c["head_dim"]
    H, Hkv = p["q"]["wmat"].shape[1], p["k"]["wmat"].shape[1]
    k = rotary(mm("bse,ehd->bshd", x, p["k"]["wmat"]), c, kind)
    v = mm("bse,ehd->bshd", x, p["v"]["wmat"])
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    keep = j <= i
    if kind == "sliding_attention" and VARIANT != "window_off":
        window = c["sliding_window"] - (1 if VARIANT == "window_511" else 0)
        keep = keep & (j > i - window)
    reads = np.arange(H) % Hkv if VARIANT == "kv_mod" \
        else np.arange(H) // (H // Hkv)

    @jax.checkpoint
    def head(w_q, w_g, w_o, kv):
        q = rotary(mm("bse,ed->bsd", x, w_q)[:, :, None, :], c, kind)[:, :, 0]
        s = mm("bqd,bkd->bqk", q, k[:, :, kv]) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        o = mm("bqk,bkd->bqd", pr, v[:, :, kv])
        if VARIANT != "no_gate":
            o = jax.nn.sigmoid(mm("bse,e->bs", x, w_g))[..., None] * o
        return mm("bsd,de->bse", o, w_o)

    total, _ = jax.lax.scan(
        lambda acc, w: (acc + head(*w), None), jnp.zeros_like(x),
        (jnp.moveaxis(p["q"]["wmat"], 1, 0),
         jnp.moveaxis(p["gate"]["wmat"], 1, 0), p["o"]["wmat"],
         jnp.asarray(reads, jnp.int32)))
    return total


def route(p, x, c):
    """Gates ``(N, X)`` — zero where an expert was not chosen — for
    positions ``x`` (N, E)."""
    k = c["num_experts_per_tok"] - (1 if VARIANT == "top9" else 0)
    logits = jnp.einsum("ne,ex->nx", x, p["router"]["wmat"],
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits) if VARIANT == "sigmoid_scores" \
        else jax.nn.softmax(logits, axis=-1)
    kth = jnp.sort(s, axis=1)[:, -k][:, None]
    gates = jnp.where(s >= kth, s, 0.0)
    if c["norm_topk_prob"] and VARIANT != "no_renorm":
        gates = gates / jnp.sum(gates, axis=1, keepdims=True)
    if VARIANT != "no_scaling":
        gates = gates * c["moe_routed_scaling_factor"]
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
    if VARIANT != "no_shared":
        out = out + swiglu(xf, p["shared"])
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


def is_dense(c, i):
    return i in c["mlp_only_layers"] or (i + 1) % c["decoder_sparse_step"]


def stages(c):
    """``[(name, {part: layer}, fn)]``: the model as a chain. ``fn(p, x,
    tokens, target) -> (y, loss)`` with ``p`` the weights by PART, so
    that stages which differ in nothing but their weights — the window
    layers' attention halves, the expert halves — are one function and
    compile once."""
    eps, out = c["rms_norm_eps"], []

    def embed(p, x, tokens, target):
        return p["embed"]["wmat"][tokens], 0.0

    def attn_half(kind):
        def fn(p, x, tokens, target):
            return x + attention(p["attn"], rms(x, p["ln1"]["gamma"], eps),
                                 c, kind), 0.0
        return fn

    def dense_half(p, x, tokens, target):
        return x + swiglu(rms(x, p["ln2"]["gamma"], eps), p["ffn"]), 0.0

    def expert_half(p, x, tokens, target):
        return x + experts(p["moe"], rms(x, p["ln2"]["gamma"], eps), c), 0.0

    def head(p, x, tokens, target):
        h = rms(x, p["norm"]["gamma"], eps)
        return h, head_loss(h, p["head"]["wmat"], target)

    halves = {kind: attn_half(kind)
              for kind in ("full_attention", "sliding_attention")}
    out.append(("embed", {"embed": "tok_embed"}, embed))
    for i in range(c["num_hidden_layers"]):
        pre = f"b{i}"
        out.append((pre + "_attn", {"ln1": pre + "_ln1",
                                    "attn": pre + "_attn"},
                    halves[c["layer_types"][i]]))
        if is_dense(c, i):
            out.append((pre + "_mlp", {"ln2": pre + "_ln2",
                                       "ffn": pre + "_ffn"}, dense_half))
        else:
            out.append((pre + "_mlp", {"ln2": pre + "_ln2",
                                       "moe": pre + "_moe"}, expert_half))
    out.append(("head", {"norm": "final_norm", "head": "lm_head"}, head))
    return out


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
            def bwd(p, x, tokens, target, gy):
                (y, loss), vjp = jax.vjp(
                    lambda p_, x_: fn(p_, x_, tokens, target), p, x)
                return vjp((gy, jnp.ones_like(loss)))
            self._jits[fn] = jax.jit(fn), jax.jit(bwd)
        return self._jits[fn]

    @staticmethod
    def _weights(params, own):
        return {part: params[layer] for part, layer in own.items()}

    def forward(self, params, tokens, label):
        """-> (the loss, every stage's input)."""
        x, xs, loss = np.zeros((), np.float32), [], 0.0
        with jax.default_matmul_precision("highest"):
            for i, (name, own, fn) in enumerate(self.stages):
                xs.append(x)
                y, part = self._fns(i)[0](self._weights(params, own), x,
                                          tokens, label)
                x = np.asarray(y)
                loss += float(part)
        return loss, xs

    def backward(self, params, tokens, label, xs):
        """Gradients by layer name (host)."""
        grads = {}
        # the chain's last output feeds nothing
        gy = np.zeros(xs[-1].shape, np.float32)
        with jax.default_matmul_precision("highest"):
            for i in reversed(range(len(self.stages))):
                name, own, fn = self.stages[i]
                gp, gx = self._fns(i)[1](self._weights(params, own), xs[i],
                                         tokens, label, gy)
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
    """The losses of ``steps`` steps of Adam from ``params``."""
    zeros = lambda t: jax.tree_util.tree_map(np.zeros_like, t)
    m1, m2 = zeros(params), zeros(params)
    losses = []
    for t in range(1, steps + 1):
        loss, xs = model.forward(params, tokens, label)
        losses.append(loss)
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


def group_of(key):
    """Which line of ``compared`` a leaf's gradient norm belongs to."""
    layer, rest = key.split("/", 1)
    if rest.startswith("router"):
        return "routers"
    if rest.startswith("gate"):
        return "gates"
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


def _moe_names(c):
    return [f"b{i}_moe" for i in range(c["num_hidden_layers"])
            if not is_dense(c, i)]


def initial_params(tr, seed):
    """The weights the trainer started from, on the host: the program's
    initialiser under the conf's seed, run once more (the step donated
    the arrays themselves; jitted as the trainer's own call is, so the
    executable is the one it built)."""
    return _host(jax.jit(tr.net.init)(jax.random.PRNGKey(seed))[0])


def timed_step(tr, batch):
    """One more step of the timed path's own ``update`` on ``batch`` (the
    class's method: a harness that wrapped the instance's counts its own
    steps) -> ``(the step's loss, its train metric's loss, the l2 norm of
    the step's gradient by layer and leaf)``. Adam's first moment is ``m1
    <- m1 + d1 (g - m1)``, so the step's gradient is what it did to
    ``m1``, leaf by leaf — from the executable the window runs, at no
    second compile and with no copy of the weights on the device. The
    metric's loss is the step's own ``seq_logloss``, reported as at a
    round's end before the step and after it: the window's round starts
    clean."""
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
        raise ValueError(f"references/laguna_s_2_1.py has no check {kind!r}")
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

    # 1. three steps of Adam from the initial weights against the losses
    #    the timed path's first three steps gave
    seed = int(dict(view["defaults"]).get("seed", 0))
    params0 = initial_params(tr, seed)
    mark("initial_weights")
    losses = train_steps(model, params0, tokens, label, lr)
    del params0
    mark("three_steps")
    for t, want in enumerate(losses):
        got = view["warm_losses"][t]
        said[f"loss_step{t + 1}_program"] = got
        said[f"loss_step{t + 1}_reference"] = want
        hold(f"loss_step{t + 1}_abs_diff", abs(got - want), lim["loss_abs"])
    # 2. one more step of the timed path, at the weights as the warm-up
    #    left them: its loss and every leaf's gradient norm against the
    #    reference's forward and backward at the same weights
    now = {name: _host(leaves) for name, leaves in tr.params.items()}
    p_loss, p_metric, got = timed_step(tr, batch)
    mark("program_probe")
    r_loss, xs = model.forward(now, tokens, label)
    grads = model.backward(now, tokens, label, xs)
    del now, xs
    mark("reference_probe")
    view["say"](reference_check_seconds=seconds)
    said["probe_loss_program"] = p_loss
    said["probe_loss_metric_program"] = p_metric
    said["probe_loss_reference"] = r_loss
    hold("probe_loss_abs_diff", abs(p_loss - r_loss), lim["probe_loss_abs"])
    hold("probe_loss_metric_abs_diff", abs(p_metric - r_loss),
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


def attended_pairs(c, positions: int, kind: str) -> float:
    """(query, key) pairs one head of a layer of type ``kind`` attends on
    one row: the causal triangle, or the band inside it."""
    w = positions if kind == "full_attention" \
        else min(c["sliding_window"], positions)
    return w * (w + 1) / 2 + (positions - w) * w


def attention_layers(c, kind: str):
    """The query-head counts of the layers of type ``kind``."""
    return [c["num_attention_heads_per_layer"][i]
            for i in range(c["num_hidden_layers"])
            if c["layer_types"][i] == kind]


def attention_flops(c, positions: int, kind: str) -> float:
    """The products of ALL layers of type ``kind`` on one row, forward:
    q.k and p.v over ``head_dim`` each, two operations a multiply-add,
    over the pairs each head attends."""
    return 2.0 * attended_pairs(c, positions, kind) * 2 * c["head_dim"] \
        * sum(attention_layers(c, kind))


def expert_pair_flops(c) -> float:
    """One (position, expert) pair through one routed expert, forward."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def matrix_params_per_position(c) -> float:
    """Parameters of the matrix products one position passes through,
    forward, the held experts' by the EXPECTED pairs a position (``topk x
    held / all``) so that the count does not move with the routing. The
    embedding is a gather and the norms are bandwidth: neither counts."""
    E, d, Hkv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    pairs = c["num_experts_per_tok"] * c["num_experts"] \
        / c["num_experts_published"]
    moe = E * c["num_experts_published"] + 3 * E * (
        c["shared_expert_intermediate_size"]
        + c["moe_intermediate_size"] * pairs)
    total = float(E * c["vocab_size"])
    for i in range(c["num_hidden_layers"]):
        H = c["num_attention_heads_per_layer"][i]
        total += 2 * E * H * d + 2 * E * Hkv * d + E * H
        total += 3 * E * c["intermediate_size"] if is_dense(c, i) else moe
    return total


def step_flops(c, rows: int, positions: int) -> float:
    """Forward, and a backward of two products per forward product:
    6 x parameters a position passes through, plus the attention
    products over the pairs each layer attends (causal, or in the band),
    forward once and backward twice."""
    return rows * (6.0 * positions * matrix_params_per_position(c)
                   + 3.0 * sum(attention_flops(c, positions, kind) for kind
                               in ("full_attention", "sliding_attention")))


def train_step_flops(view: dict) -> float:
    positions = int(view["config"]["input_shape"][-1])
    return step_flops(view["config"], view["rows"], positions)
