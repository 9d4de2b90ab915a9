"""Fused BatchNorm + activation: Pallas TPU kernels + jnp reference.

The dominant non-conv HBM traffic of the flagship Inception-BN step
is the conv -> batch_norm -> relu chain: the jnp
path reads the conv output for the moments, again for the normalize,
and writes the normalized activation, with the relu riding a fourth
logical pass XLA must fuse back in. The fused kernel does moments,
normalize, scale/shift, and the activation in ONE ``pallas_call``
whose HBM traffic is exactly two streaming reads of x plus one write
of y — the minimum any batch-norm can do (the mean must exist before
the first output byte) — and the backward rebuilds x_hat from saved
(mean, rstd) residuals in one fused pass of its own (two reads of
x/dy + one write of dx) instead of the 5+ reduction/elementwise
kernels the autodiff graph schedules.

Layout: activations are viewed as (N, C) rows — N = batch*H*W for
conv nodes, N = batch for flat nodes — with per-channel statistics
reduced over rows. The row dimension is tiled (``fused.row_block``);
the channel dimension stays whole in VMEM (C is at most a few
thousand for every shipped config).

Variance options (the ADVICE r5 fold-in):

* ``two_pass=False`` (default, reference parity): one-pass
  E[x^2]-E[x]^2 with a clamp at 0 — grid of 2 row-sweeps.
* ``two_pass=True``: numerically-robust E[(x-mean)^2] — grid of 3
  row-sweeps (one extra streaming read of x, no cancellation risk).

``fused_bn_act`` returns ``(y, mean, var)`` or ``None`` when the
shape/dtype is unsupported (caller falls back to its jnp reference).
``mean``/``var`` feed the layer's running-stat EMA only and are
treated as non-differentiable by the custom_vjp (their cotangents are
structurally zero: no loss reads them).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused import (FusedSpmd, batch_divisible, island, note_fallback,
                    note_fused, out_struct, row_block, sublane_mult,
                    supported_dtype, use_interpret)


def bn_act_reference(x: jax.Array, gamma: jax.Array, beta: jax.Array,
                     eps: float, act: str = "none",
                     two_pass: bool = False):
    """Golden jnp implementation on NHWC/flat nodes: returns
    ``(y, mean, var)`` with f32 per-channel stats over all leading
    axes, matching layers/norm.py's training math exactly."""
    axes = tuple(range(x.ndim - 1))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    if two_pass:
        var = jnp.mean(jnp.square(xf - mean), axis=axes)
    else:
        var = jnp.maximum(
            jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean), 0.0)
    inv = jax.lax.rsqrt(var + eps)
    out = (x - mean) * inv * gamma + beta
    if act == "relu":
        out = jax.nn.relu(out)
    return out.astype(x.dtype), mean, var


# -- forward kernel -----------------------------------------------------------

def _bn_fwd_kernel(x_ref, gamma_ref, beta_ref, y_ref, mean_ref, var_ref,
                   acc1, acc2, *, nb, n_total, eps, act, two_pass):
    """Row-sweep phases over grid (2*nb,) or (3*nb,) — the x BlockSpec
    maps every phase back onto the same nb row blocks, so x streams
    through VMEM once per sweep while the (1, C) accumulators persist
    in scratch across the whole grid (flash-attention pattern)."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc1[...] = jnp.zeros_like(acc1)
        acc2[...] = jnp.zeros_like(acc2)

    if two_pass:
        @pl.when(j < nb)
        def _sum():
            xb = x_ref[...].astype(jnp.float32)
            acc1[...] += jnp.sum(xb, axis=0, keepdims=True)

        @pl.when(j == nb - 1)
        def _mean():
            acc1[...] = acc1[...] / n_total        # acc1 becomes mean

        @pl.when(jnp.logical_and(j >= nb, j < 2 * nb))
        def _sumsq():
            d = x_ref[...].astype(jnp.float32) - acc1[...]
            acc2[...] += jnp.sum(d * d, axis=0, keepdims=True)

        @pl.when(j == 2 * nb - 1)
        def _finish_stats():
            var = acc2[...] / n_total
            mean_ref[...] = acc1[...]
            var_ref[...] = var
            acc2[...] = jax.lax.rsqrt(var + eps)   # acc2 becomes rstd
        norm_from = 2 * nb
    else:
        @pl.when(j < nb)
        def _sums():
            xb = x_ref[...].astype(jnp.float32)
            acc1[...] += jnp.sum(xb, axis=0, keepdims=True)
            acc2[...] += jnp.sum(xb * xb, axis=0, keepdims=True)

        @pl.when(j == nb - 1)
        def _finish_stats2():
            mean = acc1[...] / n_total
            # one-pass E[x^2]-E[x]^2, clamped at 0 (f32 cancellation
            # can push it a hair negative) — layers/norm.py parity
            var = jnp.maximum(acc2[...] / n_total - mean * mean, 0.0)
            mean_ref[...] = mean
            var_ref[...] = var
            acc1[...] = mean
            acc2[...] = jax.lax.rsqrt(var + eps)   # acc2 becomes rstd
        norm_from = nb

    @pl.when(j >= norm_from)
    def _normalize():
        xb = x_ref[...].astype(jnp.float32)
        out = ((xb - acc1[...]) * acc2[...]
               * gamma_ref[...].astype(jnp.float32)
               + beta_ref[...].astype(jnp.float32))
        if act == "relu":
            out = jnp.maximum(out, 0.0)
        y_ref[...] = out.astype(y_ref.dtype)


def _bn_forward(x2, gamma, beta, eps, act, two_pass, interpret, bn):
    n, c = x2.shape
    nb = n // bn
    sweeps = 3 if two_pass else 2
    kern = functools.partial(
        _bn_fwd_kernel, nb=nb, n_total=float(n), eps=eps, act=act,
        two_pass=two_pass)
    row_spec = pl.BlockSpec((bn, c), lambda j: (j % nb, 0))
    vec_spec = pl.BlockSpec((1, c), lambda j: (0, 0))
    y, mean, var = pl.pallas_call(
        kern,
        grid=(sweeps * nb,),
        in_specs=[row_spec, vec_spec, vec_spec],
        out_specs=[row_spec, vec_spec, vec_spec],
        out_shape=[out_struct((n, c), x2.dtype, x2),
                   out_struct((1, c), jnp.float32, x2),
                   out_struct((1, c), jnp.float32, x2)],
        scratch_shapes=[pltpu.VMEM((1, c), jnp.float32),
                        pltpu.VMEM((1, c), jnp.float32)],
        interpret=interpret, name="bn_act_fwd",
    )(x2, gamma.reshape(1, c), beta.reshape(1, c))
    return y, mean, var


# -- backward kernel ----------------------------------------------------------

def _bn_bwd_kernel(*refs, nb, n_total, act):
    """Two row sweeps: (1) reduce sum(dy') and sum(dy'*x_hat) per
    channel (dy' = dy masked by the activation), (2) the fused dx
    formula. dgamma/dbeta fall out of the phase-1 reductions."""
    if act == "relu":
        (x_ref, dy_ref, y_ref, gamma_ref, mean_ref, rstd_ref,
         dx_ref, dgamma_ref, dbeta_ref, sb, sxh) = refs
    else:
        (x_ref, dy_ref, gamma_ref, mean_ref, rstd_ref,
         dx_ref, dgamma_ref, dbeta_ref, sb, sxh) = refs
        y_ref = None
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        sb[...] = jnp.zeros_like(sb)
        sxh[...] = jnp.zeros_like(sxh)

    def _dyp_xhat():
        dyb = dy_ref[...].astype(jnp.float32)
        if y_ref is not None:
            dyb = jnp.where(y_ref[...].astype(jnp.float32) > 0.0, dyb, 0.0)
        xh = ((x_ref[...].astype(jnp.float32) - mean_ref[...])
              * rstd_ref[...])
        return dyb, xh

    @pl.when(j < nb)
    def _reduce():
        dyb, xh = _dyp_xhat()
        sb[...] += jnp.sum(dyb, axis=0, keepdims=True)
        sxh[...] += jnp.sum(dyb * xh, axis=0, keepdims=True)

    @pl.when(j == nb - 1)
    def _grads():
        dgamma_ref[...] = sxh[...]
        dbeta_ref[...] = sb[...]

    @pl.when(j >= nb)
    def _dx():
        dyb, xh = _dyp_xhat()
        g = gamma_ref[...].astype(jnp.float32) * rstd_ref[...]
        dx = g * (dyb - sb[...] / n_total - xh * (sxh[...] / n_total))
        dx_ref[...] = dx.astype(dx_ref.dtype)


def _bn_backward(x2, gamma, mean, rstd, y2, dy2, act, interpret, bn):
    n, c = x2.shape
    nb = n // bn
    kern = functools.partial(_bn_bwd_kernel, nb=nb, n_total=float(n),
                             act=act)
    row_spec = pl.BlockSpec((bn, c), lambda j: (j % nb, 0))
    vec_spec = pl.BlockSpec((1, c), lambda j: (0, 0))
    ins = [x2, dy2] + ([y2] if act == "relu" else [])
    ins += [gamma.reshape(1, c), mean, rstd]
    in_specs = [row_spec, row_spec] + \
        ([row_spec] if act == "relu" else []) + [vec_spec] * 3
    dx, dgamma, dbeta = pl.pallas_call(
        kern,
        grid=(2 * nb,),
        in_specs=in_specs,
        out_specs=[row_spec, vec_spec, vec_spec],
        out_shape=[out_struct((n, c), x2.dtype, x2),
                   out_struct((1, c), jnp.float32, x2),
                   out_struct((1, c), jnp.float32, x2)],
        scratch_shapes=[pltpu.VMEM((1, c), jnp.float32),
                        pltpu.VMEM((1, c), jnp.float32)],
        interpret=interpret, name="bn_act_bwd",
    )(*ins)
    return dx, dgamma, dbeta


# -- custom_vjp wrapper -------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _bn_act_2d(x2, gamma, beta, eps, act, two_pass, interpret, bn):
    y, mean, var = _bn_forward(x2, gamma, beta, eps, act, two_pass,
                               interpret, bn)
    return y, mean, var


def _bn_act_fwd(x2, gamma, beta, eps, act, two_pass, interpret, bn):
    y, mean, var = _bn_forward(x2, gamma, beta, eps, act, two_pass,
                               interpret, bn)
    rstd = jax.lax.rsqrt(var + eps)
    res = (x2, gamma, mean, rstd, y if act == "relu" else None)
    return (y, mean, var), res


def _bn_act_bwd(eps, act, two_pass, interpret, bn, res, cts):
    # cts = (dy, dmean, dvar); mean/var feed the running-stat EMA only
    # (carried state, never read by the loss), so their cotangents are
    # structurally zero and are dropped here — same contract as
    # flash_attention's lse output.
    x2, gamma, mean, rstd, y2 = res
    dy = cts[0]
    dx, dgamma, dbeta = _bn_backward(x2, gamma, mean, rstd, y2, dy, act,
                                     interpret, bn)
    return (dx, dgamma.reshape(gamma.shape).astype(gamma.dtype),
            dbeta.reshape(gamma.shape).astype(gamma.dtype))


_bn_act_2d.defvjp(_bn_act_fwd, _bn_act_bwd)


# -- mesh (shard_map island) variant ------------------------------------------
#
# On a dp mesh the single fused kernel cannot stand: its moments would
# be shard-local where the jnp path's jnp.mean is a cross-replica
# sync-BN collective, and GSPMD cannot shard the opaque pallas_call
# anyway. The mesh variant splits the moment pass from the normalize
# pass around an explicit psum over the data axis, all inside one
# fully-manual shard_map island: per shard the HBM traffic is still
# two streaming reads of x plus one write of y (the single-device
# minimum), and the psum'd sums make fused BN on a dp mesh match the
# global-moment jnp reference bit-for-bit in fp32 whenever the sums
# themselves are exact (integer-valued activations; pinned by
# tests/test_fused_mesh.py) and to f32 rounding otherwise. The
# backward's cross-shard reductions (dgamma/dbeta and the dx formula's
# sum terms) psum the same way. custom_vjp sits OUTSIDE the islands —
# fwd and bwd are each their own shard_map — so autodiff never
# transposes a shard_map.

def _bn_sums_kernel(x_ref, s1_ref, s2_ref, acc1, acc2, *, nb):
    """One streaming read: per-channel local (sum, sum of squares)."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc1[...] = jnp.zeros_like(acc1)
        acc2[...] = jnp.zeros_like(acc2)
    xb = x_ref[...].astype(jnp.float32)
    acc1[...] += jnp.sum(xb, axis=0, keepdims=True)
    acc2[...] += jnp.sum(xb * xb, axis=0, keepdims=True)

    @pl.when(j == nb - 1)
    def _finish():
        s1_ref[...] = acc1[...]
        s2_ref[...] = acc2[...]


def _bn_norm_kernel(x_ref, gamma_ref, beta_ref, mean_ref, rstd_ref,
                    y_ref, *, act):
    """Second read + the write: normalize/scale/shift (+relu) with the
    (already global) mean/rstd handed in as (1, C) rows."""
    xb = x_ref[...].astype(jnp.float32)
    out = ((xb - mean_ref[...]) * rstd_ref[...]
           * gamma_ref[...].astype(jnp.float32)
           + beta_ref[...].astype(jnp.float32))
    if act == "relu":
        out = jnp.maximum(out, 0.0)
    y_ref[...] = out.astype(y_ref.dtype)


def _bn_bwd_sums_kernel(*refs, nb, act):
    """Local backward reductions: per-channel sum(dy') and
    sum(dy'*x_hat), dy' masked by the activation."""
    if act == "relu":
        x_ref, dy_ref, y_ref, mean_ref, rstd_ref, sb_ref, sxh_ref, \
            ab, axh = refs
    else:
        x_ref, dy_ref, mean_ref, rstd_ref, sb_ref, sxh_ref, ab, axh = refs
        y_ref = None
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        ab[...] = jnp.zeros_like(ab)
        axh[...] = jnp.zeros_like(axh)
    dyb = dy_ref[...].astype(jnp.float32)
    if y_ref is not None:
        dyb = jnp.where(y_ref[...].astype(jnp.float32) > 0.0, dyb, 0.0)
    xh = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * rstd_ref[...]
    ab[...] += jnp.sum(dyb, axis=0, keepdims=True)
    axh[...] += jnp.sum(dyb * xh, axis=0, keepdims=True)

    @pl.when(j == nb - 1)
    def _finish():
        sb_ref[...] = ab[...]
        sxh_ref[...] = axh[...]


def _bn_bwd_dx_kernel(*refs, act):
    """dx from the fused formula, with the mean-normalized GLOBAL
    reduction terms (sb/n, sxh/n) handed in as (1, C) rows."""
    if act == "relu":
        (x_ref, dy_ref, y_ref, gamma_ref, mean_ref, rstd_ref,
         sbn_ref, sxhn_ref, dx_ref) = refs
    else:
        (x_ref, dy_ref, gamma_ref, mean_ref, rstd_ref,
         sbn_ref, sxhn_ref, dx_ref) = refs
        y_ref = None
    dyb = dy_ref[...].astype(jnp.float32)
    if y_ref is not None:
        dyb = jnp.where(y_ref[...].astype(jnp.float32) > 0.0, dyb, 0.0)
    xh = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * rstd_ref[...]
    g = gamma_ref[...].astype(jnp.float32) * rstd_ref[...]
    dx = g * (dyb - sbn_ref[...] - xh * sxhn_ref[...])
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _row_vec_specs(bn, c):
    return (pl.BlockSpec((bn, c), lambda j: (j, 0)),
            pl.BlockSpec((1, c), lambda j: (0, 0)))


def _mesh_fwd_local(x, gamma, beta, *, c, eps, act, interpret, bn, axis,
                    n_total):
    """Island body (local shard): pallas sums -> psum -> global
    moments -> pallas normalize."""
    x2 = x.reshape(-1, c)
    n, _ = x2.shape
    nb = n // bn
    row, vec = _row_vec_specs(bn, c)
    s1, s2 = pl.pallas_call(
        functools.partial(_bn_sums_kernel, nb=nb),
        grid=(nb,), in_specs=[row], out_specs=[vec, vec],
        out_shape=[out_struct((1, c), jnp.float32, x2)] * 2,
        scratch_shapes=[pltpu.VMEM((1, c), jnp.float32)] * 2,
        interpret=interpret, name="bn_act_fwd_sums")(x2)
    s1 = jax.lax.psum(s1, axis)
    s2 = jax.lax.psum(s2, axis)
    mean = s1 / n_total
    # one-pass E[x^2]-E[x]^2 with the same clamp as the jnp reference
    var = jnp.maximum(s2 / n_total - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    y2 = pl.pallas_call(
        functools.partial(_bn_norm_kernel, act=act),
        grid=(nb,), in_specs=[row, vec, vec, vec, vec], out_specs=row,
        out_shape=out_struct(x2.shape, x2.dtype, x2),
        interpret=interpret, name="bn_act_fwd_norm")(
            x2, gamma.reshape(1, c), beta.reshape(1, c), mean, rstd)
    return (y2.reshape(x.shape), mean.reshape(c), var.reshape(c),
            rstd.reshape(c))


def _mesh_bwd_local(x, dy, y, gamma, mean, rstd, *, c, act, interpret,
                    bn, axis, n_total):
    """Island body (local shard): pallas reductions -> psum -> pallas
    dx; dgamma/dbeta are the psum'd (global) reductions."""
    x2 = x.reshape(-1, c)
    dy2 = dy.reshape(-1, c)
    n, _ = x2.shape
    nb = n // bn
    row, vec = _row_vec_specs(bn, c)
    mean_r, rstd_r = mean.reshape(1, c), rstd.reshape(1, c)
    ins = [x2, dy2] + ([y.reshape(-1, c)] if act == "relu" else []) \
        + [mean_r, rstd_r]
    in_specs = [row, row] + ([row] if act == "relu" else []) + [vec, vec]
    sb, sxh = pl.pallas_call(
        functools.partial(_bn_bwd_sums_kernel, nb=nb, act=act),
        grid=(nb,), in_specs=in_specs, out_specs=[vec, vec],
        out_shape=[out_struct((1, c), jnp.float32, x2)] * 2,
        scratch_shapes=[pltpu.VMEM((1, c), jnp.float32)] * 2,
        interpret=interpret, name="bn_act_bwd_sums")(*ins)
    sb = jax.lax.psum(sb, axis)
    sxh = jax.lax.psum(sxh, axis)
    ins2 = [x2, dy2] + ([y.reshape(-1, c)] if act == "relu" else []) \
        + [gamma.reshape(1, c), mean_r, rstd_r, sb / n_total,
           sxh / n_total]
    in_specs2 = [row, row] + ([row] if act == "relu" else []) \
        + [vec] * 5
    dx2 = pl.pallas_call(
        functools.partial(_bn_bwd_dx_kernel, act=act),
        grid=(nb,), in_specs=in_specs2, out_specs=row,
        out_shape=out_struct(x2.shape, x2.dtype, x2),
        interpret=interpret, name="bn_act_bwd_dx")(*ins2)
    return dx2.reshape(x.shape), sxh.reshape(c), sb.reshape(c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _bn_act_mesh(x, gamma, beta, eps, act, interpret, bn, spmd, n_total):
    y, mean, var, _ = island(
        spmd, functools.partial(
            _mesh_fwd_local, c=x.shape[-1], eps=eps, act=act,
            interpret=interpret, bn=bn, axis=spmd.batch_axis,
            n_total=n_total),
        in_batch=(True, False, False),
        out_batch=(True, False, False, False),
        interpret=interpret)(x, gamma, beta)
    return y, mean, var


def _bn_act_mesh_fwd(x, gamma, beta, eps, act, interpret, bn, spmd,
                     n_total):
    y, mean, var, rstd = island(
        spmd, functools.partial(
            _mesh_fwd_local, c=x.shape[-1], eps=eps, act=act,
            interpret=interpret, bn=bn, axis=spmd.batch_axis,
            n_total=n_total),
        in_batch=(True, False, False),
        out_batch=(True, False, False, False),
        interpret=interpret)(x, gamma, beta)
    res = (x, gamma, mean, rstd, y if act == "relu" else None)
    return (y, mean, var), res


def _bn_act_mesh_bwd(eps, act, interpret, bn, spmd, n_total, res, cts):
    # mean/var cotangents are structurally zero (EMA-only outputs),
    # exactly as on the single-device path
    x, gamma, mean, rstd, y = res
    dy = cts[0]
    if y is None:
        y = dy          # placeholder with the right sharding; unread
    dx, dgamma, dbeta = island(
        spmd, functools.partial(
            _mesh_bwd_local, c=x.shape[-1], act=act, interpret=interpret,
            bn=bn, axis=spmd.batch_axis, n_total=n_total),
        in_batch=(True, True, True, False, False, False),
        out_batch=(True, False, False),
        interpret=interpret)(x, dy, y, gamma, mean, rstd)
    return (dx, dgamma.reshape(gamma.shape).astype(gamma.dtype),
            dbeta.reshape(gamma.shape).astype(gamma.dtype))


_bn_act_mesh.defvjp(_bn_act_mesh_fwd, _bn_act_mesh_bwd)


def fused_bn_act(x: jax.Array, gamma: jax.Array, beta: jax.Array,
                 eps: float, act: str = "none", two_pass: bool = False,
                 interpret: Optional[bool] = None,
                 block_rows: int = 256,
                 spmd: Optional[FusedSpmd] = None):
    """Fused train-time batch norm (+ optional relu) over the trailing
    channel axis of an NHWC or flat node. Returns ``(y, mean, var)``
    with y in x.dtype and f32 stats, or ``None`` when unsupported
    (caller falls back to the jnp reference). With ``spmd`` the op
    runs as a shard_map island on the mesh — moments are psum'd over
    the data axis (sync-BN) so the math matches the GSPMD jnp path."""
    if not supported_dtype(x) or x.ndim != 4 or act not in ("none", "relu"):
        note_fallback("bn_unsupported")
        return None
    c = x.shape[-1]
    n = x.size // c
    if spmd is not None:
        if two_pass:
            # the mesh islands implement the default one-pass moments
            # only; bn_two_pass falls back to the (sync-BN) jnp path
            note_fallback("bn_two_pass_mesh")
            return None
        if not batch_divisible(spmd, x.shape[0]):
            note_fallback("bn_batch_indivisible")
            return None
        n_local = n // spmd.n_shards
    else:
        n_local = n
    # keep ~2 row blocks + accumulators comfortably inside VMEM even
    # for wide flat nodes: shrink the row tile as C grows
    target = max(8, min(block_rows, (1 << 20) // max(4 * c, 1) // 8 * 8))
    bn = row_block(n_local, target, mult=sublane_mult(x))
    if bn is None or gamma.shape != (c,) or beta.shape != (c,):
        note_fallback("bn_shape")
        return None
    with note_fused("bn_act"):
        if spmd is not None:
            y, mean, var = _bn_act_mesh(x, gamma, beta, float(eps), act,
                                        use_interpret(interpret), bn, spmd,
                                        float(n))
            return y, mean, var
        x2 = x.reshape(n, c)
        y, mean, var = _bn_act_2d(x2, gamma, beta, float(eps), act,
                                  bool(two_pass), use_interpret(interpret), bn)
        return y.reshape(x.shape), mean.reshape(c), var.reshape(c)
