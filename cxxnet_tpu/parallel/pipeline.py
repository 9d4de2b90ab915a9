"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

TPU-idiomatic extension beyond the reference (its only parallelism is data
parallel + the fullc_gather trick, SURVEY §2.4): a stack of identical
stages is sharded over a ``'pipe'`` mesh axis (one stage per device group);
the batch is split into M microbatches that flow through the ring with
``lax.ppermute`` — device p computes microbatch (t - p) at tick t, so the
pipeline fills for S-1 ticks, streams, and drains. Forward-only latency is
(M + S - 1) stage-times; autodiff through the scan + ppermute gives the
symmetric backward schedule automatically.

API: stage parameters are pytrees with a leading stage axis (S, ...);
``pipeline_apply`` runs under an existing shard_map (axis bound), and
``pipeline_sharded`` wraps one call end-to-end on a mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array, axis_name: str,
                   n_microbatch: int) -> jax.Array:
    """Run ``x`` through S pipelined stages under shard_map.

    stage_params: local stage's params (leading stage axis already split by
    shard_map, size 1) — pytree of (1, ...) arrays.
    x: the local copy of the FULL batch (replicated over the pipe axis);
    every device computes the microbatch schedule, but only applies its own
    stage. Output is the full batch after the last stage (replicated).
    """
    S = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    M = n_microbatch
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatch {M}")
    mb = B // M
    xs = x.reshape(M, mb, *x.shape[1:])
    local_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)

    perm = [(i, (i + 1) % S) for i in range(S)]

    def pvary(a):
        return lax.pcast(a, (axis_name,), to="varying")

    # per-device "current activation" register and output accumulator
    state0 = pvary(jnp.zeros((mb,) + xs.shape[2:], x.dtype))
    out0 = pvary(jnp.zeros_like(xs))

    def tick(carry, t):
        state, out = carry
        # stage 0 ingests microbatch t (when one remains); other stages use
        # the activation received from the previous stage
        feed = jnp.where(t < M, t, M - 1)
        inp = jnp.where(me == 0, xs[feed], state)
        y = stage_fn(local_params, inp)
        # last stage banks its finished microbatch (index t - (S-1))
        done_idx = jnp.clip(t - (S - 1), 0, M - 1)
        bank = jnp.logical_and(me == S - 1, t >= S - 1)
        out = lax.cond(
            bank,
            lambda o: lax.dynamic_update_slice(
                o, y[None].astype(o.dtype), (done_idx,) + (0,) * (o.ndim - 1)),
            lambda o: o, out)
        # rotate activations one hop down the pipe
        state = lax.ppermute(y, axis_name, perm)
        return (state, out), None

    (_, out), _ = lax.scan(tick, (state0, out0), jnp.arange(M + S - 1))
    # replicate the last stage's banked outputs to every pipe member so the
    # caller sees the full result regardless of position
    out = lax.psum(
        out * jnp.where(me == S - 1, 1.0, 0.0).astype(out.dtype), axis_name)
    return out.reshape(B, *out.shape[2:])


def pipeline_apply_stages(stage_fns, params: Any, x: jax.Array, aux: Any,
                          axis_name: str, n_microbatch: int,
                          boundary_sd, out_sd,
                          extra_vary_axes=(),
                          stats_sd=None):
    """GPipe schedule over HETEROGENEOUS stages (the config-driven path).

    ``stage_fns``: S callables.
    ``f_k(params, mb_input, m) -> (y, scalar, stats)`` — ``m`` is the
    microbatch index (fold it into any dropout rng so masks differ per
    microbatch). ``f_0`` ingests raw data microbatches; middle stages
    ingest the boundary activation; the LAST stage is
    ``f_{S-1}(params, inp, aux_mb, m) -> (y, scalar, stats)`` — it also
    receives its microbatch's slice of ``aux`` (labels/mask, any pytree
    with leading dim M). Every stage's per-microbatch ``scalar`` (loss
    for the last stage; auxiliary losses like MoE load-balance terms for
    body stages — return 0.0 when none) is summed over live ticks AND
    DIFFERENTIATED: the backward seeds each stage's scalar output with
    the loss cotangent, so auxiliary losses raised inside the body train
    their layers exactly as in the unsharded step. ``stats`` is a per-microbatch statistics pytree
    (batch_norm moments) with the SAME structure from every stage
    (``stats_sd`` — shape/dtype structs; pad entries a stage doesn't own
    with zeros; pass ``{}``/None when no stage has stats). Returns
    ``(out, scalar_sum, stats_sum)``: the last stage's scalars and every
    stage's stats summed over the M live microbatch ticks (drain-tick
    garbage is masked out) and psum'd over the pipe axis — so the caller
    gets replicated per-layer totals it can turn into exact full-batch
    moments. Stats receive no gradient (running statistics are auxiliary,
    exactly like the unsharded step's has_aux state).

    Keeping the loss INSIDE the last stage matters: it makes every
    collective in the step data-dependent on the ring, so no independent
    all-reduce can interleave with the ppermutes (concurrent independent
    collectives deadlock the CPU backend's in-process communicator and
    serialize badly on real ICI).

    All inter-stage boundaries share one activation shape/dtype
    (``boundary_sd``, without the microbatch dim) — the ring register
    ``lax.ppermute`` rotates; the final output (``out_sd``) may differ.
    Device p selects its own stage with ``lax.switch``, so each device
    executes exactly one stage's FLOPs per tick. ``params`` is the full
    (replicated) param tree — stage memory sharding is the stacked
    homogeneous path above (``pipeline_apply``); here throughput scales
    and per-device *activation* memory drops to one microbatch.

    The backward pass is a HAND-WRITTEN reverse schedule (custom_vjp):
    cotangents enter at the last stage and ride the inverted ring while
    each device transposes its own stage (recomputing stage activations
    from the saved tick-entry registers — remat, not storage). Plain
    autodiff is not an option: transposing a device-index ``lax.switch``
    whose branches contain pvary boundaries inserts collectives into SOME
    branches only, so devices diverge in collective order and deadlock.
    ``extra_vary_axes``: the other manual axes of the enclosing
    shard_map (data, manual-tp model, seq) — the schedule's carries vary
    over them, and the param cotangent is summed over them so it leaves
    the vjp replicated, like the params came in. Not
    twice-differentiable (the custom backward is primal-only).
    """
    S = len(stage_fns)
    M = n_microbatch
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatch {M}")
    mb = B // M
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]
    perm_inv = [(i, (i - 1) % S) for i in range(S)]
    axes = (axis_name,) + tuple(extra_vary_axes)
    reg_shape = (mb,) + tuple(boundary_sd.shape)
    out_shape = (mb,) + tuple(out_sd.shape)

    def pvary(a, want=None):
        # vary only over the axes the value is not already varying on
        # (pcast rejects mixed-state axis lists)
        want = axes if want is None else want
        have = jax.typeof(a).vma
        need = tuple(ax for ax in want if ax not in have)
        return lax.pcast(a, need, to="varying") if need else a

    if stats_sd is None:
        stats_sd = {}

    def zero_stats():
        return jax.tree_util.tree_map(
            lambda a: pvary(jnp.zeros(a.shape, a.dtype)), stats_sd)

    def aux_at(aux_, m):
        return jax.tree_util.tree_map(
            lambda a: a[jnp.clip(m, 0, M - 1)], aux_)

    def last_call(p, inp, aux_, m):
        y, scalar, st = stage_fns[S - 1](p, inp, aux_at(aux_, m), m)
        return y, jnp.asarray(scalar, jnp.float32), st

    def forward(params, x, aux_):
        me = lax.axis_index(axis_name)
        xs = x.reshape(M, mb, *x.shape[1:])
        reg0 = pvary(jnp.zeros(reg_shape, boundary_sd.dtype))
        out0 = pvary(jnp.zeros((M,) + out_shape, out_sd.dtype))
        loss0 = pvary(jnp.zeros((), jnp.float32))
        stats0 = zero_stats()

        def tick(carry, t):
            reg, out, loss, stats = carry
            feed = jnp.where(t < M, t, M - 1)
            zero_reg = pvary(jnp.zeros(reg_shape, boundary_sd.dtype))
            zero_out = pvary(jnp.zeros(out_shape, out_sd.dtype))

            def branch(k):
                def run(reg_in):
                    # stage k holds a real microbatch only in this window;
                    # fill/drain ticks recompute a clipped microbatch whose
                    # stats/scalars must not contaminate the accumulators
                    live_k = jnp.logical_and(t - k >= 0, t - k < M)
                    gate = jnp.where(live_k, 1.0, 0.0)

                    def mask_stats(st):
                        return jax.tree_util.tree_map(
                            lambda a: pvary(a * gate.astype(a.dtype)), st)

                    inp = pvary(xs[feed]) if k == 0 else reg_in
                    if k == S - 1:
                        y, scalar, st = last_call(params, inp, aux_,
                                                  t - (S - 1))
                        return (zero_reg, y.astype(zero_out.dtype),
                                pvary(scalar * gate), mask_stats(st))
                    y, scalar, st = stage_fns[k](params, inp, t - k)
                    return (y.astype(zero_reg.dtype), zero_out,
                            pvary(jnp.asarray(scalar, jnp.float32) * gate),
                            mask_stats(st))
                return run

            reg_new, bank, scalar, st_t = lax.switch(
                me, [branch(k) for k in range(S)], reg)
            done_idx = jnp.clip(t - (S - 1), 0, M - 1)
            live = jnp.logical_and(me == S - 1, t >= S - 1)
            out = lax.cond(
                live,
                lambda o: lax.dynamic_update_slice(
                    o, bank[None].astype(o.dtype),
                    (done_idx,) + (0,) * (o.ndim - 1)),
                lambda o: o, out)
            # each branch already gated its scalar by its own liveness;
            # the pipe-axis psum below merges the per-stage contributions
            loss = loss + scalar
            stats = jax.tree_util.tree_map(jnp.add, stats, st_t)
            reg_next = lax.ppermute(reg_new, axis_name, perm)
            return (reg_next, out, loss, stats), reg  # save tick-ENTRY reg

        (_, out, loss, stats), regs = lax.scan(
            tick, (reg0, out0, loss0, stats0), jnp.arange(T))
        # replicate the last stage's results to every pipe member. ONE psum
        # for all values: separate psums would be data-independent and the
        # scheduler could interleave one with the backward ring (see the
        # docstring's deadlock note). Each stage's stats live only on its
        # own device (zeros elsewhere), so the psum is also the merge.
        out, loss, stats = lax.psum(
            (out * jnp.where(me == S - 1, 1.0, 0.0).astype(out.dtype),
             loss, stats), axis_name)
        return out.reshape(B, *out.shape[2:]), loss, stats, regs

    @jax.custom_vjp
    def run(params, x, aux_):
        out, loss, stats, _ = forward(params, x, aux_)
        return out, loss, stats

    def run_fwd(params, x, aux_):
        out, loss, stats, regs = forward(params, x, aux_)
        return (out, loss, stats), (params, x, aux_, regs)

    def run_bwd(res, cot):
        # dstats is discarded: running statistics are auxiliary outputs
        # (the unsharded step's new_state is has_aux too, never a grad path)
        dout, dloss, _dstats = cot         # dloss replicated (loss is)
        params, x, aux_, regs = res
        me = lax.axis_index(axis_name)
        xs = x.reshape(M, mb, *x.shape[1:])
        dout_m = dout.reshape(M, mb, *dout.shape[1:])
        zero_dx = jnp.zeros(xs.shape[1:], xs.dtype)
        zero_db = jnp.zeros(reg_shape, boundary_sd.dtype)
        dreg0 = pvary(jnp.zeros(reg_shape, boundary_sd.dtype))
        dxs0 = pvary(jnp.zeros_like(xs))
        dp0 = jax.tree_util.tree_map(lambda a: pvary(jnp.zeros_like(a)),
                                     params)

        # params must be FULLY VARYING before entering the per-branch vjps:
        # differentiating a function that reads invariant params inside a
        # varying computation makes the transpose insert a psum_invariant
        # at the boundary — inside the switch branch — and branch-local
        # collectives deadlock (devices take different branches). With
        # varying params the vjp is collective-free and we sum explicitly
        # at the end.
        pv_params = jax.tree_util.tree_map(pvary, params)

        def rtick(carry, t):
            dreg, dp_acc, dxs = carry
            feed = jnp.where(t < M, t, M - 1)
            m_last = t - (S - 1)
            live_last = jnp.logical_and(m_last >= 0, m_last < M)
            dy_last = jnp.where(
                live_last, dout_m[jnp.clip(m_last, 0, M - 1)],
                0).astype(out_sd.dtype)
            ds_last = jnp.where(live_last, dloss, 0.0)

            def branch(k):
                def run_b(dreg_in):
                    # vary inputs OUTSIDE the vjp'd function — a pvary
                    # inside it would transpose into a psum confined to
                    # this branch, and branch-local collectives diverge
                    # across devices (the deadlock this custom vjp exists
                    # to avoid). With fully-varying inputs the primal
                    # outputs are fully varying, so cotangent types match
                    # without any pvary in the traced function.
                    inp = pvary(xs[feed] if k == 0 else regs[t])
                    if k == S - 1:
                        # [:2] drops the stats output (no cotangent; the
                        # stats computation is DCE'd from the vjp trace)
                        _, vjp = jax.vjp(
                            lambda pp, xx: last_call(pp, xx, aux_,
                                                     m_last)[:2],
                            pv_params, inp.astype(boundary_sd.dtype
                                                  if S > 1 else xs.dtype))
                        dp, dinp = vjp((pvary(dy_last),
                                        pvary(jnp.float32(ds_last))))
                    else:
                        m = t - k
                        live = jnp.logical_and(m >= 0, m < M)
                        dy = jnp.where(live, pvary(dreg_in), 0)
                        # the stage's scalar (auxiliary loss) joined the
                        # loss accumulator on live ticks — seed it with
                        # the same loss cotangent the last stage gets
                        ds = jnp.where(live, dloss, 0.0)
                        _, vjp = jax.vjp(
                            lambda pp, xx: (lambda r: (
                                r[0].astype(dy.dtype),
                                jnp.asarray(r[1], jnp.float32)))(
                                    stage_fns[k](pp, xx, m)),
                            pv_params, inp.astype(
                                xs.dtype if k == 0 else boundary_sd.dtype))
                        dp, dinp = vjp((dy, pvary(jnp.float32(ds))))
                    if k == 0:
                        return (dp, dinp.astype(zero_dx.dtype),
                                pvary(zero_db))
                    return (dp, pvary(zero_dx), dinp.astype(zero_db.dtype))
                return run_b

            dp_t, dx_t, db_t = lax.switch(
                me, [branch(k) for k in range(S)], dreg)
            dp_acc = jax.tree_util.tree_map(jnp.add, dp_acc, dp_t)
            # stage 0 banks the data cotangent for microbatch `feed`
            # (dx_t is zero on every other device and on drained ticks)
            dxs = lax.dynamic_update_slice(
                dxs, (dxs[feed] + dx_t)[None].astype(dxs.dtype),
                (feed,) + (0,) * (dxs.ndim - 1))
            dreg = lax.ppermute(db_t, axis_name, perm_inv)
            return (dreg, dp_acc, dxs), None

        (_, dp_acc, dxs), _ = lax.scan(
            rtick, (dreg0, dp0, dxs0), jnp.arange(T - 1, -1, -1))
        # params entered replicated: sum the per-device stage contributions
        # over the pipe axis so the cotangent leaves replicated too. The
        # pipe-axis psum covers dp AND dxs in one call, and the psums
        # over the remaining axes consume its result — every collective
        # in the backward chains, none can interleave with the ring.
        dp_acc, dxs = lax.psum((dp_acc, dxs), axis_name)
        # a cotangent is summed over every axis its primal is REPLICATED
        # over (each peer there holds a partial contribution: data
        # shards, manual-tp slices), and must leave typed as the primal
        # came in — custom_vjp checks the varying axes. Params vary over
        # at most the pipe axis (an all-gathered FSDP leaf is typed
        # varying there; its summed cotangent is cast to match).
        if extra_vary_axes:
            dp_acc = lax.psum(dp_acc, tuple(extra_vary_axes))
        dp_acc = jax.tree_util.tree_map(
            lambda ct, pr: pvary(ct, tuple(jax.typeof(pr).vma)),
            dp_acc, params)
        x_rep = tuple(ax for ax in extra_vary_axes
                      if ax not in jax.typeof(x).vma)
        if x_rep:
            dxs = lax.psum(dxs, x_rep)
        dx = pvary(dxs.reshape(x.shape).astype(x.dtype),
                   tuple(jax.typeof(x).vma))
        daux = jax.tree_util.tree_map(jnp.zeros_like, aux_)
        return dp_acc, dx, daux

    run.defvjp(run_fwd, run_bwd)
    return run(params, x, aux)


def pipeline_sharded(mesh: Mesh, stage_fn, stage_params, x: jax.Array,
                     n_microbatch: int, pipe_axis: str = "pipe") -> jax.Array:
    """One-call pipeline: stage_params' leading axis shards over
    ``pipe_axis``; x is replicated; returns the full-batch output."""
    pparam_spec = jax.tree_util.tree_map(
        lambda _: P(pipe_axis), stage_params)
    fn = jax.shard_map(
        functools.partial(pipeline_apply, stage_fn, axis_name=pipe_axis,
                          n_microbatch=n_microbatch),
        mesh=mesh,
        in_specs=(pparam_spec, P()),
        out_specs=P(),
    )
    return fn(stage_params, x)
