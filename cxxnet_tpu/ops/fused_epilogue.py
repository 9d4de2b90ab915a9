"""Fused bias + activation epilogue for conv/fullc outputs.

The cxxnet reference hand-fused bias-add and activation into its conv
kernels' epilogues; here the conv/matmul itself stays on XLA's MXU
lowering (it wins there) and only the epilogue — bias broadcast-add
plus the (graph-folded, see graph.act_fusion_plan) relu — runs as one
Pallas kernel: one streaming read of the conv output, one write, with
the backward fusing the dx mask and the per-channel dbias reduction
into a single pass (the autodiff graph otherwise schedules the relu
mask, the dbias reduce, and the dx select as separate HBM-visible
values in cost_analysis' accounting).

Views everything as (N, C) rows like the other fused ops. ``act`` may
be "relu" or "none"; ``bias`` may be None (act-only epilogue — the
no_bias conv -> relu case). Returns ``None`` when unsupported or when
there is nothing to fuse (no bias AND no act).
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


def bias_act_reference(x: jax.Array, bias: Optional[jax.Array],
                       act: str = "none") -> jax.Array:
    """Golden jnp implementation, matching the layers' existing math
    (bias cast to the activation dtype before the add)."""
    y = x if bias is None else x + bias.astype(x.dtype)
    if act == "relu":
        y = jax.nn.relu(y)
    return y


def _epi_fwd_kernel(*refs, act, has_bias):
    if has_bias:
        x_ref, b_ref, y_ref = refs
        y = x_ref[...] + b_ref[...].astype(x_ref.dtype)
    else:
        x_ref, y_ref = refs
        y = x_ref[...]
    if act == "relu":
        y = jnp.maximum(y, 0)
    y_ref[...] = y


def _epi_bwd_kernel(*refs, act, has_bias, nb):
    """dx per block; dbias accumulates across the (sequential) grid in
    scratch and lands in its (1, C) output at the last step."""
    if has_bias:
        y_ref, dy_ref, dx_ref, db_ref, acc = refs
    else:
        y_ref, dy_ref, dx_ref = refs
        db_ref = acc = None
    j = pl.program_id(0)
    # masked in f32 whatever the storage dtype: the v5e has no bf16
    # vector compare (the widening and the cast back are exact)
    dyb = dy_ref[...].astype(jnp.float32)
    if act == "relu":
        dyb = jnp.where(y_ref[...].astype(jnp.float32) > 0.0, dyb, 0.0)
    dx_ref[...] = dyb.astype(dx_ref.dtype)
    if has_bias:
        @pl.when(j == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)
        acc[...] += jnp.sum(dyb, axis=0, keepdims=True)

        @pl.when(j == nb - 1)
        def _finish():
            db_ref[...] = acc[...]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _epi_act_2d(x2, act, interpret, bn):
    """act-only epilogue (no bias)."""
    n, c = x2.shape
    return pl.pallas_call(
        functools.partial(_epi_fwd_kernel, act=act, has_bias=False),
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda j: (j, 0))],
        out_specs=pl.BlockSpec((bn, c), lambda j: (j, 0)),
        out_shape=out_struct((n, c), x2.dtype, x2),
        interpret=interpret, name="bias_act_fwd_act",
    )(x2)


def _epi_act_fwd(x2, act, interpret, bn):
    y = _epi_act_2d(x2, act, interpret, bn)
    return y, y


def _epi_act_bwd(act, interpret, bn, y, dy):
    n, c = y.shape
    dx = pl.pallas_call(
        functools.partial(_epi_bwd_kernel, act=act, has_bias=False,
                          nb=n // bn),
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda j: (j, 0)),
                  pl.BlockSpec((bn, c), lambda j: (j, 0))],
        out_specs=pl.BlockSpec((bn, c), lambda j: (j, 0)),
        out_shape=out_struct((n, c), y.dtype, y, dy),
        interpret=interpret, name="bias_act_bwd_act",
    )(y, dy)
    return (dx,)


_epi_act_2d.defvjp(_epi_act_fwd, _epi_act_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _epi_bias_2d(x2, bias, act, interpret, bn):
    n, c = x2.shape
    return pl.pallas_call(
        functools.partial(_epi_fwd_kernel, act=act, has_bias=True),
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda j: (j, 0)),
                  pl.BlockSpec((1, c), lambda j: (0, 0))],
        out_specs=pl.BlockSpec((bn, c), lambda j: (j, 0)),
        out_shape=out_struct((n, c), x2.dtype, x2),
        interpret=interpret, name="bias_act_fwd",
    )(x2, bias.reshape(1, c))


def _epi_bias_fwd(x2, bias, act, interpret, bn):
    y = _epi_bias_2d(x2, bias, act, interpret, bn)
    return y, (y, bias)


def _epi_bias_bwd(act, interpret, bn, res, dy):
    y, bias = res
    n, c = y.shape
    dx, db = pl.pallas_call(
        functools.partial(_epi_bwd_kernel, act=act, has_bias=True,
                          nb=n // bn),
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda j: (j, 0)),
                  pl.BlockSpec((bn, c), lambda j: (j, 0))],
        out_specs=[pl.BlockSpec((bn, c), lambda j: (j, 0)),
                   pl.BlockSpec((1, c), lambda j: (0, 0))],
        out_shape=[out_struct((n, c), y.dtype, y, dy),
                   out_struct((1, c), jnp.float32, y, dy)],
        scratch_shapes=[pltpu.VMEM((1, c), jnp.float32)],
        interpret=interpret, name="bias_act_bwd",
    )(y, dy)
    return dx, db.reshape(bias.shape).astype(bias.dtype)


_epi_bias_2d.defvjp(_epi_bias_fwd, _epi_bias_bwd)


# -- mesh (shard_map island) variant ------------------------------------------
#
# Bias + act over a batch-sharded node: fwd/bwd pallas calls each run
# inside their own fully-manual island (custom_vjp OUTSIDE the
# shard_map), and the only collective is the backward's dbias psum
# over the data axis — a replicated bias's gradient is the sum of the
# shard-local column reductions. Act-only epilogues have no
# replicated operand at all and simply island-wrap the existing
# custom_vjp (all specs batch-sharded, so the shard_map transpose is
# collective-free and exact).

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _epi_bias_mesh(x, bias, act, interpret, bn, spmd):
    c = x.shape[-1]
    return island(
        spmd, lambda xl, bl: _epi_bias_2d(
            xl.reshape(-1, c), bl, act, interpret, bn
        ).reshape(xl.shape),
        in_batch=(True, False), out_batch=True,
        interpret=interpret)(x, bias)


def _epi_bias_mesh_fwd(x, bias, act, interpret, bn, spmd):
    y = _epi_bias_mesh(x, bias, act, interpret, bn, spmd)
    return y, (y, bias)


def _epi_bias_mesh_bwd(act, interpret, bn, spmd, res, dy):
    y, bias = res
    c = y.shape[-1]

    def local(yl, dyl):
        n = yl.size // c
        dx2, db = pl.pallas_call(
            functools.partial(_epi_bwd_kernel, act=act, has_bias=True,
                              nb=n // bn),
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, c), lambda j: (j, 0)),
                      pl.BlockSpec((bn, c), lambda j: (j, 0))],
            out_specs=[pl.BlockSpec((bn, c), lambda j: (j, 0)),
                       pl.BlockSpec((1, c), lambda j: (0, 0))],
            out_shape=[out_struct((n, c), yl.dtype, yl, dyl),
                       out_struct((1, c), jnp.float32, yl, dyl)],
            scratch_shapes=[pltpu.VMEM((1, c), jnp.float32)],
            interpret=interpret, name="bias_act_bwd",
        )(yl.reshape(n, c), dyl.reshape(n, c))
        db = jax.lax.psum(db, spmd.batch_axis)
        return dx2.reshape(yl.shape), db
    dx, db = island(spmd, local, in_batch=(True, True),
                    out_batch=(True, False), interpret=interpret)(y, dy)
    return dx, db.reshape(bias.shape).astype(bias.dtype)


_epi_bias_mesh.defvjp(_epi_bias_mesh_fwd, _epi_bias_mesh_bwd)


def fused_bias_act(x: jax.Array, bias: Optional[jax.Array],
                   act: str = "none", interpret: Optional[bool] = None,
                   block_rows: int = 512,
                   spmd: Optional[FusedSpmd] = None):
    """Fused epilogue on an NHWC/flat node's trailing channel axis.
    Returns y (x.dtype) or ``None`` when unsupported / nothing to
    fuse. With ``spmd`` the kernels run as shard_map islands on the
    mesh (dbias psum'd over the data axis in the backward)."""
    if bias is None and act == "none":
        return None                      # nothing to fuse
    if not supported_dtype(x) or x.ndim != 4 or act not in ("none", "relu"):
        note_fallback("epilogue_unsupported")
        return None
    c = x.shape[-1]
    n = x.size // c
    if spmd is not None:
        if not batch_divisible(spmd, x.shape[0]):
            note_fallback("epilogue_batch_indivisible")
            return None
        n_local = n // spmd.n_shards
    else:
        n_local = n
    target = max(8, min(block_rows, (1 << 20) // max(4 * c, 1) // 8 * 8))
    bn = row_block(n_local, target, mult=sublane_mult(x))
    if bn is None or (bias is not None and bias.shape != (c,)):
        note_fallback("epilogue_shape")
        return None
    with note_fused("bias_act"):
        itp = use_interpret(interpret)
        if spmd is not None:
            if bias is None:
                return island(
                    spmd, lambda xl: _epi_act_2d(
                        xl.reshape(-1, c), act, itp, bn).reshape(xl.shape),
                    in_batch=(True,), out_batch=True, interpret=itp)(x)
            return _epi_bias_mesh(x, bias, act, itp, bn, spmd)
        x2 = x.reshape(n, c)
        if bias is None:
            y = _epi_act_2d(x2, act, itp, bn)
        else:
            y = _epi_bias_2d(x2, bias, act, itp, bn)
        return y.reshape(x.shape)
