"""Optimizers (updaters) with tag-scoped hyperparameters and LR schedules.

Reference: /root/reference/src/updater/ — SGDUpdater (sgd_updater-inl.hpp:29-88),
NAGUpdater (nag_updater-inl.hpp:17-74), AdamUpdater (adam_updater-inl.hpp:18-84),
UpdaterParam schedules + tag scoping (param.h:12-136). The reference creates one
updater object per weight tensor; here the optimizer is a pure pytree transform
applied inside the jitted train step — hyperparameters are resolved per leaf by
its tag ('wmat'/'bias'), schedule scalars are computed host-side per epoch and
passed in as traced scalars so LR changes never trigger recompilation.

Deviation from reference: AdamUpdater applies weight decay as ``grad -= wd*w``
(adam_updater-inl.hpp:76, sign bug); here decay is standard ``grad += wd*w``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ConfigPairs

TAGS = ("wmat", "bias")


def tag_for_param(param_name: str) -> str:
    """lr/wd scoping group for a parameter leaf key (reference updater key
    encoding, updater.h:150-173). LayerNorm gamma/beta follow the bias
    group so weight decay never pulls the multiplicative gamma toward 0.
    Single source of truth — Network.param_tag delegates here."""
    return "bias" if param_name in ("bias", "gamma", "beta") else "wmat"


@dataclasses.dataclass
class UpdaterHyper:
    """Per-tag hyperparameters (reference UpdaterParam)."""
    tag: str = "wmat"
    base_lr: float = 0.01
    wd: float = 0.0
    momentum: float = 0.9
    lr_schedule: int = 0          # 0 const, 1 expdecay, 2 polydecay, 3 factor
    lr_step: int = 1
    lr_gamma: float = 0.5
    lr_alpha: float = 0.5
    lr_factor: float = 0.1
    lr_minimum: float = 1e-5
    start_epoch: int = 0
    momentum_schedule: int = 0
    base_momentum: float = 0.5
    final_momentum: float = 0.9
    saturation_epoch: int = 0
    clip_gradient: float = 0.0
    beta1_decay: float = 0.1      # adam: beta1 = 1 - beta1_decay
    beta2_decay: float = 0.001

    def set_param(self, name: str, val: str) -> None:
        # tag scoping: "wmat:lr" applies only when tag == "wmat" (param.h:113-117)
        if name.startswith(self.tag + ":"):
            name = name[len(self.tag) + 1:]
        elif ":" in name and name.split(":", 1)[0] in TAGS:
            return  # scoped to a different tag
        if name in ("lr", "eta"):
            self.base_lr = float(val)
        elif name == "wd":
            self.wd = float(val)
        elif name == "momentum":
            self.momentum = float(val)
        elif name == "momentum_schedule":
            self.momentum_schedule = int(val)
        elif name == "clip_gradient":
            self.clip_gradient = float(val)
        elif name == "final_momentum":
            self.final_momentum = float(val)
        elif name == "base_momentum":
            self.base_momentum = float(val)
        elif name == "saturation_epoch":
            self.saturation_epoch = int(val)
        elif name == "beta1":
            self.beta1_decay = float(val)
        elif name == "beta2":
            self.beta2_decay = float(val)
        elif name.startswith("lr:") or name.startswith("eta:"):
            sub = name.split(":", 1)[1]
            if sub == "schedule":
                mapping = {"constant": 0, "expdecay": 1, "polydecay": 2,
                           "factor": 3}
                if val in mapping:
                    self.lr_schedule = mapping[val]
            elif sub == "gamma":
                self.lr_gamma = float(val)
            elif sub == "alpha":
                self.lr_alpha = float(val)
            elif sub == "step":
                self.lr_step = int(val)
            elif sub == "factor":
                self.lr_factor = float(val)
            elif sub == "minimum_lr":
                self.lr_minimum = float(val)
            elif sub == "start_epoch":
                self.start_epoch = int(val)

    def schedule(self, epoch: int) -> Tuple[float, float]:
        """(learning_rate, momentum) at update-step ``epoch``
        (reference ScheduleEpoch, param.h:78-98)."""
        if self.lr_schedule == 0:
            lr = self.base_lr
        elif self.lr_schedule == 1:
            lr = self.base_lr * (self.lr_gamma ** (epoch / self.lr_step))
        elif self.lr_schedule == 2:
            lr = self.base_lr * (1.0 + (epoch // self.lr_step) * self.lr_gamma) \
                ** (-self.lr_alpha)
        elif self.lr_schedule == 3:
            lr = self.base_lr * (self.lr_factor ** (epoch // self.lr_step))
        else:
            raise ValueError("unknown lr schedule")
        momentum = self.momentum
        if self.momentum_schedule and self.saturation_epoch:
            momentum = (self.final_momentum - self.base_momentum) \
                / self.saturation_epoch * epoch + self.base_momentum
        momentum = min(momentum, self.final_momentum) \
            if self.momentum_schedule else momentum
        lr = max(lr, self.lr_minimum)
        if epoch < self.start_epoch:
            lr = self.base_lr
        return lr, momentum


def build_hypers(cfg: ConfigPairs) -> Dict[str, UpdaterHyper]:
    hypers = {tag: UpdaterHyper(tag=tag) for tag in TAGS}
    for name, val in cfg:
        for h in hypers.values():
            h.set_param(name, val)
    return hypers


def _prep_grad(g, w, hyper: UpdaterHyper):
    """NaN-zeroing clip (reference struct clip, sgd_updater-inl.hpp:17-25).
    Gradients are upcast to the master-param dtype first: under a reduced
    compute policy the per-param astype transpose already yields fp32
    grads, but a custom layer returning compute-dtype leaves must not
    drag the fp32 masters down through the update arithmetic."""
    g = g.astype(jnp.asarray(w).dtype)
    g = jnp.where(jnp.isnan(g), 0.0, g)
    if hyper.clip_gradient != 0.0:
        g = jnp.clip(g, -hyper.clip_gradient, hyper.clip_gradient)
    if hyper.wd != 0.0:
        g = g + hyper.wd * w
    return g


def _map_leaves(fn, n_out: int, *trees):
    """Map ``fn(leaf_key, *leaves) -> n_out values`` over parallel nested
    dicts, returning n_out trees with the shared structure."""
    outs = tuple({} for _ in range(n_out))
    first = trees[0]
    for k, v in first.items():
        if isinstance(v, dict):
            subs = _map_leaves(fn, n_out, *(t[k] for t in trees))
            for o, s in zip(outs, subs):
                o[k] = s
        else:
            res = fn(k, *(t[k] for t in trees))
            if n_out == 1:
                res = (res,)
            for o, r in zip(outs, res):
                o[k] = r
    return outs if n_out > 1 else outs[0]


class Optimizer:
    """Pure pytree optimizer dispatching per-leaf by tag; the leaf's dict key
    ('wmat'/'bias') selects the hyperparameter group.

    Mixed precision (``compute_dtype = float16``): the optimizer owns the
    dynamic loss scaler. Its state is a tiny ``"_mp"`` subtree of
    ``opt_state`` ({scale fp32, good int32}) so it rides every step
    family's carry (std jit, sp/pp shard_map, train_chain scan) with no
    extra dispatch and checkpoints with the rest of the optimizer state.
    ``update`` then unscales the incoming (loss-scaled) gradients, skips
    the apply and halves the scale on any inf/nan, and doubles the scale
    after ``loss_scale_window`` consecutive clean applies. bf16 shares
    fp32's exponent range and needs none of this (``fp16`` stays False).
    """

    def __init__(self, updater_type: str, cfg: ConfigPairs):
        self.type = updater_type
        if updater_type not in ("sgd", "nag", "adam"):
            raise ValueError(f"unknown updater {updater_type!r}")
        self.hypers = build_hypers(cfg)
        from .graph import global_param, policy_from_config
        self.fp16 = policy_from_config(cfg).needs_loss_scale
        self.ls_init = float(global_param(cfg, "loss_scale_init",
                                          str(2.0 ** 15)))
        self.ls_window = int(global_param(cfg, "loss_scale_window", "200"))
        self.ls_min = float(global_param(cfg, "loss_scale_min", "1.0"))
        self.ls_max = float(global_param(cfg, "loss_scale_max",
                                         str(2.0 ** 24)))
        # sentinel LR-backoff hook: multiplies every tag's scheduled lr
        # (main.py halves it per rollback via the lr_backoff knob); the
        # trainer's schedule caches key on VALUES so a change propagates
        # without recompiling the step
        self.lr_scale = 1.0

    # -- state -------------------------------------------------------------
    def _mp_init(self) -> Dict[str, jax.Array]:
        return {"scale": jnp.float32(self.ls_init),
                "good": jnp.zeros((), jnp.int32)}

    def init_state(self, params) -> Dict[str, Any]:
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        if self.type == "adam":
            state = {"m1": zeros,
                     "m2": jax.tree_util.tree_map(jnp.zeros_like, params),
                     "t": jnp.zeros((), jnp.int32)}
        else:
            state = {"mom": zeros}
        if self.fp16:
            state["_mp"] = self._mp_init()
        return state

    def adapt_state(self, opt_state):
        """Reconcile a loaded/legacy opt state with the current policy:
        inject fresh loss-scaler state when fp16 training resumes from a
        non-fp16 checkpoint, drop it on the way back — either way the
        momentum masters restore untouched (checkpoints stay
        dtype-portable)."""
        has = isinstance(opt_state, dict) and "_mp" in opt_state
        if self.fp16 and not has:
            return {**opt_state, "_mp": self._mp_init()}
        if not self.fp16 and has:
            return {k: v for k, v in opt_state.items() if k != "_mp"}
        return opt_state

    def _tag(self, param_name: str) -> str:
        return tag_for_param(param_name)

    def state_pspecs(self, param_pspecs):
        """PartitionSpec tree matching init_state(): momentum/moment buffers
        shard exactly like their params; scalar counters replicate."""
        if self.type == "adam":
            specs = {"m1": param_pspecs, "m2": param_pspecs, "t": None}
        else:
            specs = {"mom": param_pspecs}
        if self.fp16:
            specs["_mp"] = {"scale": None, "good": None}
        return specs

    def schedules(self, epoch: int) -> Dict[str, Tuple[float, float]]:
        """Host-side schedule evaluation; pass the result into update()."""
        out = {}
        for tag, h in self.hypers.items():
            lr, mom = h.schedule(epoch)
            out[tag] = (lr * self.lr_scale, mom)
        return out

    # -- model-health stats (telemetry/modelhealth.py) ---------------------
    def health_update_stats(self, params_before, params_after,
                            eps: float = 1e-12):
        """Per-leaf update-to-weight RMS ratio of the APPLIED delta —
        ``rms(w_new - w_old) / rms(w_old)``, keyed "layer/param". The
        optimizer owns the semantics: an fp16 overflow skip or a
        non-boundary accumulation step applied nothing, so the ratio is
        exactly 0 there (the probe treats 0 as "skipped", not
        "vanished"). Healthy SGD-family training sits around 1e-4..1e-2;
        a sustained excursion out of the configured band is the
        update-dynamics anomaly the PaLM/OPT-style run logs watch.
        Pure jnp — called inside the compiled train step."""
        pairs, _ = jax.tree_util.tree_flatten_with_path(params_before)
        after = jax.tree_util.tree_leaves(params_after)
        out = {}
        for (path, b), a in zip(pairs, after):
            b32 = b.astype(jnp.float32)
            d = a.astype(jnp.float32) - b32
            key = "/".join(str(getattr(p, "key", p)) for p in path)
            out[key] = {"ratio": jnp.sqrt(jnp.mean(jnp.square(d)))
                        / (jnp.sqrt(jnp.mean(jnp.square(b32))) + eps)}
        return out

    def health_scaler_stats(self, opt_state):
        """fp16 loss-scaler numerics for the health tree: the post-step
        scale (halvings between syncs show as a scale drop). Empty for
        bf16/fp32 policies — the health-off/fp32 jaxpr carries nothing.
        Pure jnp — called inside the compiled train step."""
        if isinstance(opt_state, dict) and "_mp" in opt_state:
            return {"loss_scale":
                    opt_state["_mp"]["scale"].astype(jnp.float32)}
        return {}

    # -- update ------------------------------------------------------------
    def update(self, params, grads, opt_state, sched: Dict[str, Any],
               finite_axes: Tuple[str, ...] = ()):
        """Apply one optimizer step. ``sched[tag] = (lr, momentum)`` may be
        python floats or traced scalars. Params may be nested dicts of any
        depth (e.g. pairtest layers hold {'master': {...}, 'slave': {...}});
        the leaf's dict key determines its tag.

        fp16 policy: ``grads`` arrive loss-scaled; they are upcast to the
        fp32 masters' dtype and unscaled here, the apply is skipped (and
        the scale halved) when any gradient is non-finite, and the scale
        doubles after ``loss_scale_window`` clean applies. ``finite_axes``
        names manual mesh axes over which gradient leaves are SHARDED
        (the pp step's FSDP 'pipe' axis) — the overflow flag must agree
        across them or shards would take different cond branches and the
        params would silently diverge; replicated-grad axes (data/seq/
        model, already psum'd) need no entry."""
        mp = opt_state.get("_mp") if isinstance(opt_state, dict) else None
        if mp is not None:
            return self._update_scaled(params, grads, opt_state, sched,
                                       finite_axes)
        return self._apply(params, grads, opt_state, sched)

    def _update_scaled(self, params, grads, opt_state, sched, finite_axes):
        mp = opt_state["_mp"]
        scale = mp["scale"]
        # upcast to the fp32 masters BEFORE unscaling: an fp16 leaf (if a
        # layer ever returned one) would overflow at large scales
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) / scale, grads)
        finite = jnp.array(True)
        for g in jax.tree_util.tree_leaves(grads):
            finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
        for ax in finite_axes:
            # pmin over bool-as-f32: 1.0 only when EVERY shard is clean
            finite = jax.lax.pmin(finite.astype(jnp.float32), ax) > 0.5
        rest = {k: v for k, v in opt_state.items() if k != "_mp"}
        new_params, new_rest = jax.lax.cond(
            finite,
            lambda args: self._apply(*args),
            lambda args: (args[0], args[2]),
            (params, grads, rest, sched))
        good = jnp.where(finite, mp["good"] + 1, jnp.int32(0))
        grow = jnp.logical_and(finite, good >= self.ls_window)
        new_scale = jnp.where(
            finite,
            jnp.where(grow, jnp.minimum(scale * 2.0, self.ls_max), scale),
            jnp.maximum(scale * 0.5, self.ls_min))
        good = jnp.where(grow, jnp.int32(0), good)
        new_rest = dict(new_rest)
        new_rest["_mp"] = {"scale": new_scale, "good": good}
        return new_params, new_rest

    def _apply(self, params, grads, opt_state, sched: Dict[str, Any]):
        """The raw (unscaled, always-applied) optimizer step."""
        if self.type == "adam":
            t = opt_state["t"] + 1

            def leaf(key, w, g, m1, m2):
                h = self.hypers[self._tag(key)]
                g = _prep_grad(g, w, h)
                d1, d2 = h.beta1_decay, h.beta2_decay
                tf = t.astype(jnp.float32)
                fix1 = 1.0 - (1.0 - d1) ** tf
                fix2 = 1.0 - (1.0 - d2) ** tf
                lr, _ = sched[self._tag(key)]
                lr_t = lr * jnp.sqrt(fix2) / fix1
                n_m1 = m1 + d1 * (g - m1)
                n_m2 = m2 + d2 * (jnp.square(g) - m2)
                return w - lr_t * n_m1 / (jnp.sqrt(n_m2) + 1e-8), n_m1, n_m2

            new_params, new_m1, new_m2 = _map_leaves(
                leaf, 3, params, grads, opt_state["m1"], opt_state["m2"])
            return new_params, {"m1": new_m1, "m2": new_m2, "t": t}

        # sgd / nag
        def leaf(key, w, g, mom):
            h = self.hypers[self._tag(key)]
            lr, momentum = sched[self._tag(key)]
            g = _prep_grad(g, w, h)
            new_m = momentum * mom - lr * g
            if self.type == "sgd":
                new_w = w + new_m
            else:  # nag (nag_updater-inl.hpp:66-73)
                new_w = w + (1 + momentum) * new_m - momentum * mom
            return new_w, new_m

        new_params, new_mom = _map_leaves(leaf, 2, params, grads,
                                          opt_state["mom"])
        return new_params, {"mom": new_mom}


def create_optimizer(updater_type: str, cfg: ConfigPairs) -> Optimizer:
    return Optimizer(updater_type, cfg)
