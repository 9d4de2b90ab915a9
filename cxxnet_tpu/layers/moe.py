"""Mixture-of-Experts layer (expert parallelism).

TPU-idiomatic extension beyond the reference (no MoE exists there; the
closest spirit is fullc_gather's hybrid data/model parallelism,
/root/reference/src/updater/async_updater-inl.hpp:68-94): a token-choice
top-k routed expert FFN in the GShard/Switch formulation — dense dispatch/
combine one-hot tensors with a fixed per-expert capacity so every shape is
static for XLA. Expert weights carry a leading expert axis sharded over the
mesh 'model' axis; under pjit, GSPMD lowers the dispatch/combine einsums to
the expert all-to-all over ICI.

Config (sequence node (E,S,1) -> (E,S,1)):
  ``num_expert``, ``topk`` (1 or 2 under this capacity router; any
  number up to ``num_expert`` under the two no-drop routers below),
  ``nhidden`` (expert inner dim),
  ``capacity_factor`` (default 1.25), ``act`` (gelu/relu),
  ``moe_loss_coef`` (load-balance aux loss weight, default 0.01),
  ``no_drop`` (1 = dense all-expert evaluation, no token ever dropped —
  X/topk more expert FLOPs; for eval/correctness baselines).

The load-balancing auxiliary loss (mean fraction-routed * mean gate prob
per expert, scaled by num_expert) rides the layer state under
``_aux_loss`` and is added to the training objective by Network.apply.

``router = sigmoid`` is the other formulation (the DeepSeek-V3 family's
``noaux_tc``), with no capacity and no dropped token:

  s = sigmoid(x W_r) over ALL ``num_expert`` experts, in float32
  chosen = top-``topk`` of (s + b)      b: per-expert selection bias,
                                         layer state, never a gradient
  g_i = s_i / sum_{j in chosen} s_j * ``routed_scaling_factor``
  y = shared(x) + sum_{i in chosen and held} g_i E_i(x)

with ``E_i`` (and the ``shared_expert`` shared ones, as one expert of
that many times the width) SwiGLU without bias, ``nhidden`` wide.
``expert_first`` / ``expert_held`` name the contiguous range of experts
THIS chip holds (all of them when left out): the layer routes over all,
computes the pairs (position, expert) whose expert it holds, and hands
the partial sum on — one chip's share of an expert-parallel group; no
code stands in for the other chips or their exchange. Every held pair
is computed whatever the imbalance: pairs are sorted by expert and the
experts' products run as grouped matrix products over the groups' true
sizes (``jax.lax.ragged_dot``), on a buffer that is the smallest rung
that holds the step's held pairs; the last rung has room for every pair
the held experts can get (positions x min(topk, held)), so no pair is
dropped and the step is one executable whatever the routing. The ladder
(:func:`buffer_ladder`) comes from the layer's own sizes — twice the
balanced share of the pairs, then doubling — and the rung is chosen on
the device from the held pairs' count; a layer that holds half the
experts or more has one rung and traces no conditional. After a
training step's routing ``b_i <- b_i - bias_update_rate * sign(load_i -
mean load)`` over all experts; there is no auxiliary loss.

``router = softmax_nodrop`` is the same path under another score
function and nothing else: ``s = softmax(x W_r)`` over all experts in
float32, chosen = top-``topk`` of ``s`` itself — no selection bias, so
no ``sel_bias`` in the layer's state and nothing updated after a step —
and the same ``g_i``, held share, shared expert, sort, ladder and
``stats`` (``router = softmax`` alone stays the capacity router above).
The layer
state also carries ``stats``: pairs held, pairs routed elsewhere, pairs
dropped (0 by construction), the largest held expert's load over the
mean load of all experts, max |b| and the rows of the rung taken — the
trainer adds them to the telemetry registry when it drains the train
metric (``cxxnet_moe_*``; ``cxxnet_moe_buffer_rows{layer}`` and
``cxxnet_moe_full_buffer_steps_total`` say how often the ladder
engages).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .base import Layer, register_layer
from .seq import _seq, _unseq, swiglu

#: the score function of each no-drop router (``router = <key>``) and
#: whether the choice adds a selection bias that is layer state
_NO_DROP_ROUTERS = {"sigmoid": (jax.nn.sigmoid, True),
                    "softmax_nodrop": (
                        functools.partial(jax.nn.softmax, axis=-1), False)}

#: the order of a no-drop layer's ``stats`` vector
MOE_STATS = ("pairs_held", "pairs_elsewhere", "pairs_dropped",
             "load_max_over_mean", "sel_bias_absmax", "buffer_rows")


def buffer_ladder(n, k, held, x):
    """The sizes the expert block's buffers can take, smallest first:
    with the balanced share ``S = n k held / x`` of the ``n k`` pairs,
    ``2S, 4S, 8S, ..`` (a balanced load scatters around ``S``, which
    itself would be missed every other step), each rounded up to whole
    tiles of 8 rows, and last the room for every pair the held experts
    can get, ``n min(k, held)``. One rung where ``2S`` reaches it
    (``held >= x / 2``: the uncut layer)."""
    full = n * min(k, held)
    rungs, m = [], 2 * n * k * held // x
    while (rung := -(-m // 8) * 8) < full:
        rungs.append(rung)
        m *= 2
    return tuple(rungs) + (full,)


def _sum_slots(table, inv, n):
    """Rows of ``table`` (M, E), sorted pairs, back at their positions
    and summed over each position's slots in float32: (n, E). ``inv``
    (n K,) is a pair's row among the sorted; a pair whose row is past
    the table is not held (held pairs sort first) and reads zero."""
    back = jnp.take(table, inv, axis=0, mode="fill", fill_value=0)
    return jnp.sum(back.reshape(n, -1, table.shape[1])
                   .astype(jnp.float32), axis=1)


def _rung_inputs(m, n_held, xf, gate, order):
    """What a rung of ``m`` rows reads of the routing: the rows'
    positions, which rows are live, their positions' rows of ``xf`` and
    their gates (0 on a row that is not live)."""
    rows = order[:m]
    pos = rows // gate.shape[1]
    live = jnp.arange(m) < n_held
    with jax.named_scope("moe.experts"):
        xs = xf[pos]
    with jax.named_scope("moe.combine"):
        g_rows = jnp.where(live, gate.reshape(-1)[rows], 0.0)
    return pos, live, xs, g_rows


def _rung_products(xs, g_rows, w_gate, w_up, w_down, sizes, live):
    """The held experts' SwiGLU of the sorted rows under their gates."""
    with jax.named_scope("moe.experts"):
        ys = grouped_swiglu(xs, w_gate, w_up, w_down, sizes, live)
    with jax.named_scope("moe.combine"):
        return (ys.astype(jnp.float32) * g_rows[:, None]).astype(xs.dtype)


def _rung_fwd(m, n_held, xf, gate, order, inv, sizes, *weights):
    _, live, xs, g_rows = _rung_inputs(m, n_held, xf, gate, order)
    ys = _rung_products(xs, g_rows, *weights, sizes, live)
    with jax.named_scope("moe.combine"):
        return _sum_slots(ys, inv, xf.shape[0])


def _rung_bwd(m, res, g):
    """One rung's backward from the routing and the weights alone: it
    rebuilds its gate and up products, so no rung hands another a
    residual. Every transposed gather is a gather: the cotangent's rows
    come by position, the rows' gradients go back through ``inv``."""
    n_held, xf, gate, order, inv, sizes, *weights = res
    pos, live, xs, g_rows = _rung_inputs(m, n_held, xf, gate, order)
    _, vjp = jax.vjp(lambda *a: _rung_products(*a, sizes, live),
                     xs, g_rows, *weights)
    with jax.named_scope("moe.combine"):
        d_ys = g.astype(xs.dtype)[pos]
    d_xs, d_g_rows, *d_w = vjp(d_ys)
    with jax.named_scope("moe.experts"):
        d_xf = _sum_slots(d_xs, inv, xf.shape[0]).astype(xf.dtype)
    with jax.named_scope("moe.combine"):
        d_gate = jnp.take(d_g_rows, inv, mode="fill", fill_value=0)
    return (d_xf, d_gate.reshape(gate.shape), *d_w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def held_experts(rungs, n_held, xf, gate, order, inv, sizes, w_gate, w_up,
                 w_down):
    """The routed part of a no-drop layer on this chip, (N, E) in
    float32: rows ``xf`` (N, E) under gates ``gate`` (N, K), the pairs
    sorted by ``order`` (inverse ``inv``) with the held ones first,
    ``sizes`` (held,) to a held expert and ``n_held`` in all. The rows'
    gather, the three grouped products, the gates and the sum over slots
    run on a buffer of the first of ``rungs`` that holds ``n_held``
    pairs, chosen on the device; the last has room for every pair.

    One differentiation rule around both conditionals: differentiated as
    it stands, a ``lax.switch`` makes every branch emit zeros for every
    other branch's residuals — the last rung's full-size buffers, written
    on every step. Here the forward's residuals are its own inputs."""
    return _held_fwd(rungs, n_held, xf, gate, order, inv, sizes, w_gate,
                     w_up, w_down)[0]


def _rung_taken(rungs, n_held):
    """The index of the first rung that holds ``n_held`` pairs."""
    return jnp.sum(n_held > jnp.asarray(rungs[:-1], jnp.int32))


@functools.lru_cache(maxsize=None)
def _branches(branch, rungs):
    """``branch`` at each rung, the same functions at every call: JAX
    keeps a branch's trace by the function, so a net's expert blocks of
    one shape trace their rungs once (5 s of the cell's set-up)."""
    return tuple(functools.partial(branch, m) for m in rungs)


def _on_rung(rungs, n_held, branch, *operands):
    if len(rungs) == 1:
        return branch(rungs[0], *operands)
    return lax.switch(_rung_taken(rungs, n_held), _branches(branch, rungs),
                      *operands)


def _held_fwd(rungs, n_held, *args):
    return _on_rung(rungs, n_held, _rung_fwd, n_held, *args), \
        (n_held, *args)


def _held_bwd(rungs, res, g):
    d_xf, d_gate, *d_w = _on_rung(rungs, res[0], _rung_bwd, res, g)
    if len(rungs) > 1:
        # XLA moves a conditional's users into its branches: the weight
        # gradients' casts to the parameters' float32, which then stand
        # alone in every branch and write twice the bytes, where outside
        # they fuse into the optimizer's update (2.4 ms a step and
        # 0.3 GB of the 680 M-parameter cell; PERF.md section 6, PR 31)
        d_w = lax.optimization_barrier(d_w)
    return (None, d_xf, d_gate, None, None, None, *d_w)


held_experts.defvjp(_held_fwd, _held_bwd)


def grouped_swiglu(xs, w_gate, w_up, w_down, group_sizes, live):
    """SwiGLU of rows ``xs`` (M, E), sorted by group, through the groups'
    own weights (G, E, F) / (G, F, E): three grouped matrix products over
    the groups' true sizes (``jax.lax.ragged_dot``: on a TPU XLA's own
    grouped kernel, which beat JAX's Pallas one 2.6x at the cell's
    shapes; PERF.md section 6, PR 28). ``live`` (M,) marks the rows that
    belong to a group. A row past the last group belongs to none, and on
    a TPU the kernel leaves it UNWRITTEN, forward and backward — whatever
    was in that memory, NaN included — so every product's input and
    output is zeroed there: the mask's own transpose then zeroes the
    rows' cotangents on the way in and their gradients on the way out."""
    keep = lambda a: jnp.where(live[:, None], a, jnp.zeros((), a.dtype))
    dot = lambda a, w: keep(lax.ragged_dot(keep(a), w, group_sizes))
    h = jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up)
    return dot(h, w_down)


@register_layer("moe")
class MoELayer(Layer):
    has_params = True
    has_state = True
    # admissible in a pipeline-parallel body: the load-balance aux loss
    # rides the schedule's per-stage scalar accumulator (differentiated —
    # pipeline_apply_stages seeds every stage's scalar with the loss
    # cotangent), written via ctx.stat_sink under key "_aux:<name>"
    pp_aux_loss = True

    def _emit_aux(self, aux, ctx):
        """Deliver the aux loss: through the stat sink inside a pipeline
        stage (Network.apply_stage discards layer state), as layer state
        on the standard path (Network.apply adds state['_aux_loss'])."""
        if ctx.stat_sink is not None:
            ctx.stat_sink["_aux:" + self.name] = aux
            return {}
        return {"_aux_loss": aux}

    def set_param(self, name, val):
        if name == "num_expert":
            self.num_expert = int(val)
        elif name == "topk":
            self.topk = int(val)
        elif name == "capacity_factor":
            self.capacity_factor = float(val)
        elif name == "act":
            if val not in ("gelu", "relu", "swiglu"):
                raise ValueError(f"unknown moe act {val!r}")
            self.act = val
        elif name == "moe_loss_coef":
            self.moe_loss_coef = float(val)
        elif name == "no_drop":
            self.no_drop = int(val)
        elif name == "router":
            if val != "softmax" and val not in _NO_DROP_ROUTERS:
                raise ValueError(f"unknown moe router {val!r}")
            self.router = val
        elif name in ("shared_expert", "expert_first", "expert_held"):
            setattr(self, name, int(val))
        elif name in ("routed_scaling_factor", "bias_update_rate"):
            setattr(self, name, float(val))

    def __init__(self, spec, global_cfg):
        self.num_expert = 8
        self.topk = 2
        self.capacity_factor = 1.25
        self.act = "gelu"
        self.moe_loss_coef = 0.01
        self.no_drop = 0
        self.router = "softmax"
        self.shared_expert = 0
        self.expert_first, self.expert_held = 0, 0
        self.routed_scaling_factor = 1.0
        self.bias_update_rate = 0.001
        super().__init__(spec, global_cfg)
        self.no_drop_router = self.router in _NO_DROP_ROUTERS
        if self.no_drop_router:
            self.act = "swiglu"
            self.expert_held = self.expert_held or self.num_expert
            if not 1 <= self.topk <= self.num_expert:
                raise ValueError("moe: topk must be in 1..num_expert")
            if not (0 <= self.expert_first and self.expert_first
                    + self.expert_held <= self.num_expert):
                raise ValueError(
                    f"moe {spec.name!r}: experts [{self.expert_first}, "
                    f"{self.expert_first + self.expert_held}) are not "
                    f"among {self.num_expert}")
        elif self.topk not in (1, 2):
            raise ValueError("moe: topk must be 1 or 2 under the softmax "
                             "(capacity) router; router = sigmoid and "
                             "router = softmax_nodrop take any")
        elif self.act == "swiglu":
            raise ValueError("moe: act = swiglu needs router = sigmoid or "
                             "softmax_nodrop")

    def infer_shapes(self, in_shapes):
        self.check_n(in_shapes, 1, 1)
        return [in_shapes[0]]

    def init_params(self, key, in_shapes):
        e = in_shapes[0][0]
        f = self.hp.num_hidden or 4 * e
        x = self.num_expert
        kr, k1, k2 = jax.random.split(key, 3)
        if self.no_drop_router:
            held = self.expert_held
            k1, k3, ks = jax.random.split(k1, 3)
            w = self.hp.init_weight
            p = {"router": {"wmat": w(kr, (e, x), e, x)},
                 "g": {"wmat": w(k3, (held, e, f), e, f)},
                 "h": {"wmat": w(k1, (held, e, f), e, f)},
                 "o": {"wmat": w(k2, (held, f, e), f, e)}}
            if self.shared_expert:
                fs = self.shared_expert * f
                s1, s2, s3 = jax.random.split(ks, 3)
                p["shared"] = {"g": {"wmat": w(s1, (e, fs), e, fs)},
                               "h": {"wmat": w(s2, (e, fs), e, fs)},
                               "o": {"wmat": w(s3, (fs, e), fs, e)}}
            return p
        return {
            "router": {"wmat": self.hp.init_weight(kr, (e, x), e, x)},
            "h": {"wmat": self.hp.init_weight(k1, (x, e, f), e, f),
                  "bias": jnp.zeros((x, f), jnp.float32)},
            "o": {"wmat": self.hp.init_weight(k2, (x, f, e), f, e),
                  "bias": jnp.zeros((x, e), jnp.float32)},
        }

    def param_pspecs(self):
        if self.no_drop_router:
            # one chip's share: the held experts are whole on this chip
            return {}
        # experts sharded over 'model' (expert parallelism); router replicated
        return {"h": {"wmat": ("model", None, None), "bias": ("model", None)},
                "o": {"wmat": ("model", None, None), "bias": ("model", None)}}

    def init_state(self, in_shapes):
        if self.no_drop_router:
            st = {"stats": jnp.zeros((len(MOE_STATS),), jnp.float32)}
            if _NO_DROP_ROUTERS[self.router][1]:
                st["sel_bias"] = jnp.zeros((self.num_expert,), jnp.float32)
            return st
        return {"_aux_loss": jnp.zeros((), jnp.float32)}

    def _apply_no_drop(self, params, state, inputs, ctx):
        """``router = sigmoid`` / ``softmax_nodrop``: see the module's
        header."""
        from ..ops.fused import note_grouped
        if ctx.seq_axis is not None:
            raise ValueError(f"moe: router = {self.router} has no "
                             "sequence-parallel path")
        score_fn, biased = _NO_DROP_ROUTERS[self.router]
        cd = ctx.compute_dtype
        x = _seq(inputs[0]).astype(cd)
        B, T, E = x.shape
        N, X, K = B * T, self.num_expert, self.topk
        first, held = self.expert_first, self.expert_held
        xf = x.reshape(N, E)
        sel_bias = state["sel_bias"] if biased else None
        with jax.named_scope("moe.route"):
            logits = jnp.einsum(
                "ne,ex->nx", xf.astype(jnp.float32),
                params["router"]["wmat"].astype(jnp.float32),
                precision=lax.Precision.HIGHEST)
            score = score_fn(logits)                           # (N, X)
            _, idx = lax.top_k(score + lax.stop_gradient(sel_bias)
                               if biased else score, K)
            gate = jnp.take_along_axis(score, idx, axis=1)     # (N, K)
            gate = gate / (jnp.sum(gate, axis=1, keepdims=True) + 1e-20)
            gate = gate * self.routed_scaling_factor
            # group the pairs by expert: held ones first, in expert
            # order; the others, which other chips compute, last
            flat = idx.reshape(N * K)
            is_held = (flat >= first) & (flat < first + held)
            key = jnp.where(is_held, flat - first, held)
            order = jnp.argsort(key, stable=True)              # (N*K,)
            load = jnp.zeros((X,), jnp.float32).at[flat].add(1.0)
            sizes = lax.dynamic_slice_in_dim(load, first, held) \
                .astype(jnp.int32)
            n_held = jnp.sum(sizes)
            # the inverse permutation: a pair's row among the sorted
            inv = jnp.zeros((N * K,), jnp.int32).at[order].set(
                jnp.arange(N * K, dtype=jnp.int32))
        with jax.named_scope("moe.experts"):
            note_grouped("ragged_dot")
            weights = [params[nm]["wmat"].astype(cd) for nm in "gho"]
        rungs = buffer_ladder(N, K, held, X)
        out = held_experts(rungs, n_held, xf, gate, order, inv, sizes,
                           *weights)
        if self.shared_expert:
            with jax.named_scope("moe.shared"):
                sp = params["shared"]
                out = out + swiglu(
                    xf, *(sp[k]["wmat"].astype(cd)
                          for k in ("g", "h", "o"))).astype(jnp.float32)
        out = out.astype(cd).reshape(B, T, E)
        if ctx.stat_sink is not None:      # a pipeline stage keeps no state
            return [_unseq(out)], {}
        with jax.named_scope("moe.route"):
            new_bias = sel_bias
            if biased and ctx.train and self.bias_update_rate:
                new_bias = sel_bias - self.bias_update_rate * jnp.sign(
                    load - jnp.mean(load))
            held_f = n_held.astype(jnp.float32)
            computed = jnp.minimum(held_f, float(rungs[-1]))
            taken = jnp.asarray(rungs, jnp.float32)[
                _rung_taken(rungs, n_held)]
            stats = jnp.stack([
                computed, float(N * K) - held_f, held_f - computed,
                jnp.max(sizes).astype(jnp.float32) / (N * K / X),
                jnp.max(jnp.abs(new_bias)) if biased else 0.0, taken])
        new_state = {"stats": lax.stop_gradient(stats)}
        if biased:
            new_state["sel_bias"] = lax.stop_gradient(new_bias)
        return [_unseq(out)], new_state

    def apply(self, params, state, inputs, ctx):
        if self.no_drop_router:
            return self._apply_no_drop(params, state, inputs, ctx)
        x = _seq(inputs[0]).astype(ctx.compute_dtype)   # (B, T, E)
        B, T, E = x.shape
        X = self.num_expert
        # Under sequence parallelism (ctx.seq_axis bound by shard_map) the
        # routing is GLOBAL: capacity comes from the global token count and
        # position-in-expert offsets are exchanged across shards, so token
        # dropping matches the sp=1 run exactly (not just statistically).
        sp_ax = ctx.seq_axis
        sp = lax.psum(1, sp_ax) if sp_ax is not None else 1
        C = max(1, int(T * sp / X * self.capacity_factor * self.topk))

        logits = jnp.einsum("bte,ex->btx", x.astype(jnp.float32),
                            params["router"]["wmat"].astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)          # (B, T, X)

        # top-1 (+ optional top-2) token-choice routing with capacity
        def one_hot_dispatch(gate_residual):
            idx = jnp.argmax(gate_residual, axis=-1)     # (B, T)
            oh = jax.nn.one_hot(idx, X, dtype=jnp.float32)
            return idx, oh

        idx1, oh1 = one_hot_dispatch(gates)
        sel = [(oh1, jnp.take_along_axis(gates, idx1[..., None],
                                         axis=-1)[..., 0])]
        if self.topk == 2:
            idx2, oh2 = one_hot_dispatch(gates - gates * oh1 - oh1)
            sel.append((oh2, jnp.take_along_axis(gates, idx2[..., None],
                                                 axis=-1)[..., 0]))

        # load-balance aux loss (GShard eq.4), shared by both dataflow
        # modes: X * mean_x(frac_tokens_x * mean_gate_x), with GLOBAL
        # means over any manual shard axes
        frac = jnp.mean(oh1, axis=(0, 1))
        mean_gate = jnp.mean(gates, axis=(0, 1))
        for ax in (sp_ax, ctx.data_axis):
            if ax is not None:
                frac = lax.pmean(frac, ax)
                mean_gate = lax.pmean(mean_gate, ax)
        aux = self.moe_loss_coef * X * jnp.sum(frac * mean_gate)

        if self.no_drop:
            # no-drop mode: dense evaluation — every expert runs on every
            # token and the top-k gate mask selects outputs, so NO token is
            # ever dropped regardless of load imbalance. Costs X/topk more
            # expert FLOPs than the capacity path; use for eval,
            # correctness baselines, or small expert counts.
            w = sum(oh * gate[..., None] for oh, gate in sel)   # (B,T,X)
            h = jnp.einsum("bte,xef->btxf", x,
                           params["h"]["wmat"].astype(ctx.compute_dtype))
            h = h + params["h"]["bias"].astype(ctx.compute_dtype)[None, None]
            h = jax.nn.gelu(h) if self.act == "gelu" else jax.nn.relu(h)
            y = jnp.einsum("btxf,xfe->btxe", h,
                           params["o"]["wmat"].astype(ctx.compute_dtype))
            y = y + params["o"]["bias"].astype(ctx.compute_dtype)[None, None]
            out = jnp.einsum("btx,btxe->bte", w.astype(jnp.float32),
                             y.astype(jnp.float32)).astype(ctx.compute_dtype)
            return [_unseq(out)], self._emit_aux(aux, ctx)

        # position-in-expert via cumulative sum over tokens; tokens past the
        # capacity C are dropped (standard Switch behavior, keeps shapes
        # static for XLA). prev_count carries the GLOBAL per-expert fill
        # across selection rounds.
        dispatch = jnp.zeros((B, T, X, C), jnp.float32)
        combine = jnp.zeros((B, T, X, C), jnp.float32)
        prev_count = jnp.zeros((B, X), jnp.float32)
        for oh, gate in sel:
            local_count = jnp.sum(oh, axis=1)            # (B, X)
            if sp_ax is not None:
                # earlier shards' tokens occupy earlier expert slots
                all_counts = lax.all_gather(local_count, sp_ax)  # (sp,B,X)
                before = (jnp.arange(sp) < lax.axis_index(sp_ax))
                shard_off = jnp.einsum(
                    "s,sbx->bx", before.astype(jnp.float32), all_counts)
                round_total = jnp.sum(all_counts, axis=0)
            else:
                shard_off = jnp.zeros_like(local_count)
                round_total = local_count
            base = prev_count + shard_off
            pos = jnp.cumsum(oh, axis=1) - oh + base[:, None, :]
            prev_count = prev_count + round_total
            pos_in = jnp.sum(pos * oh, axis=-1)          # (B, T)
            keep = (pos_in < C).astype(jnp.float32) * jnp.sum(oh, axis=-1)
            slot = jax.nn.one_hot(pos_in.astype(jnp.int32), C,
                                  dtype=jnp.float32)     # (B, T, C)
            d = oh[..., None] * slot[:, :, None, :] * keep[..., None, None]
            dispatch = dispatch + d
            combine = combine + d * gate[..., None, None]

        # dispatch -> per-expert capacity buffers, expert FFN, combine back.
        # Under sp the capacity axis is SHARDED across seq shards: a
        # reduce-scatter hands each shard its C/sp slice of the global
        # buffers (slots are per-expert positions, independent of which
        # shard's token fills them), the expert FFN runs on the slice —
        # cutting expert FLOPs and the (B,X,C,F) hidden activation by sp —
        # and an all-gather of the (smaller) outputs feeds the local
        # combine. sp=1 reduces to the plain dense path.
        ex_in = jnp.einsum("btxc,bte->bxce", dispatch,
                           x.astype(jnp.float32))
        pad = 0
        if sp_ax is not None:
            pad = (-C) % sp
            if pad:
                ex_in = jnp.pad(ex_in, ((0, 0), (0, 0), (0, pad), (0, 0)))
            ex_in = lax.psum_scatter(ex_in, sp_ax, scatter_dimension=2,
                                     tiled=True)        # (B, X, C'/sp, E)
        ex_in = ex_in.astype(ctx.compute_dtype)
        h = jnp.einsum("bxce,xef->bxcf", ex_in,
                       params["h"]["wmat"].astype(ctx.compute_dtype))
        h = h + params["h"]["bias"].astype(ctx.compute_dtype)[None, :, None, :]
        h = jax.nn.gelu(h) if self.act == "gelu" else jax.nn.relu(h)
        y = jnp.einsum("bxcf,xfe->bxce", h,
                       params["o"]["wmat"].astype(ctx.compute_dtype))
        y = y + params["o"]["bias"].astype(ctx.compute_dtype)[None, :, None, :]
        if sp_ax is not None:
            y = lax.all_gather(y, sp_ax, axis=2, tiled=True)
            if pad:
                y = y[:, :, :C, :]      # padded slots are never combined
        out = jnp.einsum("btxc,bxce->bte", combine,
                         y.astype(jnp.float32)).astype(ctx.compute_dtype)

        return [_unseq(out)], self._emit_aux(aux, ctx)
