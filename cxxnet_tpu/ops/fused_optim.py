"""Fused multi-tensor optimizer apply (SGD / NAG / Adam) as Pallas
kernels.

The optimizer touches every byte of params + grads + momenta (+ Adam's
second moment) once per step — pure HBM traffic. The per-leaf pytree
walk in optim.py emits N independent elementwise chains (one per
parameter tensor: Inception-BN has ~200 leaves) that XLA schedules as
many small kernels with per-kernel launch and read/write bookkeeping;
this module instead packs each tag group's leaves into ONE flat f32
buffer per role and runs a single streaming Pallas kernel over it —
one fused pass per tag ("wmat"/"bias") instead of N per-leaf chains.

Trade-off, stated honestly: the pack (concat of raveled leaves) and
unpack (slice+reshape) around the opaque custom call are real extra
copies of the param-sized buffers that the per-leaf path does not pay,
so this trades O(params) extra bytes for O(#leaves) fewer kernel
launches. For convnet steps that is a favorable trade — param bytes
are ~1% of the flagship's activation-dominated step traffic while ~200
kernel launches are milliseconds of a ~55 ms step — but it is settled
by measurement, not assertion: the bench's ``hbm_bytes_per_step`` /
``per_step_ms`` carry the net effect, and ``fused_kernels = 0`` backs
it out if a model's params/activation ratio inverts the trade.

Semantics match optim._prep_grad + the per-leaf update exactly:
NaN-zeroing, gradient clip, weight decay, momentum/NAG or Adam with
bias correction (``lr_t`` precomputed host/trace-side — it is scalar
math). All leaves must be f32 (the master-weight dtype contract);
callers fall back to the per-leaf path otherwise.

Scalars (lr, momentum / lr_t) may be traced (the schedule is passed
into the step as traced scalars so LR changes never recompile) and
ride in as a tiny (1, 2) f32 operand.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from .fused import FusedSpmd, island, out_struct, use_interpret

_LANES = 128


def _pack(arrs: Sequence[jax.Array], block_rows: int):
    """Ravel + concat ``arrs`` into one (R, 128) f32 matrix, zero-padded
    to a whole number of (block_rows, 128) tiles. Returns (mat, total)."""
    flat = jnp.concatenate([jnp.ravel(a).astype(jnp.float32)
                            for a in arrs])
    total = flat.shape[0]
    tile = block_rows * _LANES
    padded = -(-total // tile) * tile
    if padded != total:
        flat = jnp.pad(flat, (0, padded - total))
    return flat.reshape(padded // _LANES, _LANES), total


def _unpack(mat: jax.Array, total: int, shapes, dtypes):
    flat = mat.reshape(-1)[:total]
    out, off = [], 0
    for s, d in zip(shapes, dtypes):
        n = 1
        for dim in s:
            n *= dim
        out.append(flat[off:off + n].reshape(s).astype(d))
        off += n
    return out


def _prep(g, w, wd, clip):
    """In-kernel analog of optim._prep_grad (NaN-zeroing, clip, wd)."""
    g = jnp.where(jnp.isnan(g), 0.0, g)
    if clip:
        g = jnp.clip(g, -clip, clip)
    if wd:
        g = g + wd * w
    return g


def _sgd_kernel(s_ref, w_ref, g_ref, m_ref, w_out, m_out, *,
                wd, clip, nag):
    lr = s_ref[0, 0]
    momentum = s_ref[0, 1]
    w = w_ref[...]
    m = m_ref[...]
    g = _prep(g_ref[...], w, wd, clip)
    new_m = momentum * m - lr * g
    if nag:       # nag_updater-inl.hpp:66-73
        w_out[...] = w + (1.0 + momentum) * new_m - momentum * m
    else:
        w_out[...] = w + new_m
    m_out[...] = new_m


def _adam_kernel(s_ref, w_ref, g_ref, m1_ref, m2_ref,
                 w_out, m1_out, m2_out, *, wd, clip, d1, d2):
    lr_t = s_ref[0, 0]
    w = w_ref[...]
    g = _prep(g_ref[...], w, wd, clip)
    n_m1 = m1_ref[...] + d1 * (g - m1_ref[...])
    n_m2 = m2_ref[...] + d2 * (g * g - m2_ref[...])
    w_out[...] = w - lr_t * n_m1 / (jnp.sqrt(n_m2) + 1e-8)
    m1_out[...] = n_m1
    m2_out[...] = n_m2


def _run(kern, scalars, mats, n_out, block_rows, interpret, name):
    rows = mats[0].shape[0]
    grid = (rows // block_rows,)
    row_spec = pl.BlockSpec((block_rows, _LANES), lambda j: (j, 0))
    s_spec = pl.BlockSpec((1, 2), lambda j: (0, 0))
    shape = out_struct((rows, _LANES), jnp.float32, *mats)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[s_spec] + [row_spec] * len(mats),
        out_specs=[row_spec] * n_out,
        out_shape=[shape] * n_out,
        interpret=interpret, name=name,
    )(scalars, *mats)


def fused_sgd_apply(ws: List[jax.Array], gs: List[jax.Array],
                    ms: List[jax.Array], lr, momentum, *,
                    wd: float, clip: float, nag: bool,
                    interpret: Optional[bool] = None,
                    block_rows: int = 256,
                    spmd: Optional[FusedSpmd] = None
                    ) -> Tuple[List[jax.Array], List[jax.Array]]:
    """One fused SGD/NAG momentum step over a whole tag group's leaves.
    Returns (new_ws, new_ms) with the input shapes/dtypes. With
    ``spmd`` the whole pack->kernel->unpack runs as a fully-replicated
    shard_map island: masters/grads/momenta are replicated on a dp
    mesh, every device computes the identical update, and GSPMD never
    meets the opaque pallas_call."""
    if spmd is not None:
        # lr/momentum may be traced schedule scalars: explicit island
        # inputs (replicated), never closure captures
        return island(
            spmd, lambda w_, g_, m_, lr_, mom_: fused_sgd_apply(
                w_, g_, m_, lr_, mom_, wd=wd, clip=clip, nag=nag,
                interpret=interpret, block_rows=block_rows),
            in_batch=(False,) * 5, out_batch=(False, False),
            interpret=use_interpret(interpret)
        )(list(ws), list(gs), list(ms), jnp.asarray(lr, jnp.float32),
          jnp.asarray(momentum, jnp.float32))
    shapes = [w.shape for w in ws]
    dtypes = [w.dtype for w in ws]
    wm, total = _pack(ws, block_rows)
    gm, _ = _pack(gs, block_rows)
    mm, _ = _pack(ms, block_rows)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         jnp.asarray(momentum, jnp.float32)]).reshape(1, 2)
    kern = functools.partial(_sgd_kernel, wd=float(wd), clip=float(clip),
                             nag=bool(nag))
    nw, nm = _run(kern, scalars, [wm, gm, mm], 2, block_rows,
                  use_interpret(interpret),
                  ("nag" if nag else "sgd") + "_apply_update")
    return (_unpack(nw, total, shapes, dtypes),
            _unpack(nm, total, shapes, dtypes))


def fused_adam_apply(ws: List[jax.Array], gs: List[jax.Array],
                     m1s: List[jax.Array], m2s: List[jax.Array], lr_t, *,
                     wd: float, clip: float, d1: float, d2: float,
                     interpret: Optional[bool] = None,
                     block_rows: int = 256,
                     spmd: Optional[FusedSpmd] = None):
    """One fused Adam step over a tag group (``lr_t`` already carries
    the bias correction). Returns (new_ws, new_m1s, new_m2s). With
    ``spmd``: fully-replicated shard_map island (see fused_sgd_apply)."""
    if spmd is not None:
        return island(
            spmd, lambda w_, g_, a_, b_, lr_: fused_adam_apply(
                w_, g_, a_, b_, lr_, wd=wd, clip=clip, d1=d1, d2=d2,
                interpret=interpret, block_rows=block_rows),
            in_batch=(False,) * 5, out_batch=(False, False, False),
            interpret=use_interpret(interpret)
        )(list(ws), list(gs), list(m1s), list(m2s),
          jnp.asarray(lr_t, jnp.float32))
    shapes = [w.shape for w in ws]
    dtypes = [w.dtype for w in ws]
    wm, total = _pack(ws, block_rows)
    gm, _ = _pack(gs, block_rows)
    m1m, _ = _pack(m1s, block_rows)
    m2m, _ = _pack(m2s, block_rows)
    scalars = jnp.stack([jnp.asarray(lr_t, jnp.float32),
                         jnp.zeros((), jnp.float32)]).reshape(1, 2)
    kern = functools.partial(_adam_kernel, wd=float(wd), clip=float(clip),
                             d1=float(d1), d2=float(d2))
    nw, nm1, nm2 = _run(kern, scalars, [wm, gm, m1m, m2m], 3, block_rows,
                        use_interpret(interpret), "adam_apply_update")
    return (_unpack(nw, total, shapes, dtypes),
            _unpack(nm1, total, shapes, dtypes),
            _unpack(nm2, total, shapes, dtypes))
