"""Multi-head attention: jnp reference, chunked (online-softmax), and a
Pallas TPU flash-attention kernel.

The reference framework predates attention entirely (fixed 4-D image
tensors, /root/reference/src/layer/layer.h:33-39; SURVEY §5 "long-context:
N/A"), so this module is a TPU-idiomatic extension: it makes long-context
sequence models first-class. Three interchangeable implementations, all
taking (batch, seq, heads, head_dim) arrays:

* ``attention_reference`` — plain jnp softmax(QK^T)V; O(S^2) memory.
  The golden implementation every other path is tested against.
* ``chunked_attention`` — lax.scan over key/value blocks with the online
  softmax recurrence (running max / normalizer); O(S * block_k) live
  memory, differentiable through the scan, works on any backend. This is
  also the backward path for the flash kernel.
* ``flash_attention`` — Pallas kernels tiling q into MXU-friendly blocks
  and streaming k/v blocks through VMEM. The forward also emits the
  per-row logsumexp; the backward is ONE fused kernel (``flash_bwd``)
  that rebuilds each tile's softmax once from that statistic — no
  second online pass, no chunked recompute — and feeds dq, dk and dv
  from it. The forward's output and logsumexp carry ``checkpoint_name``s
  (``FLASH_RESIDUALS``), so a ``jax.checkpoint`` that saves those names
  (``model.py`` under ``remat = 1``) does not run the forward kernel a
  second time in the backward pass. On the CPU backend the same kernels
  run under the Pallas interpreter (:func:`use_interpret`); on a TPU
  backend they are compiled. Not twice-differentiable (the fused
  backward is a kernel, not traced jnp); differentiate
  ``chunked_attention`` for higher-order uses.

Masking convention: ``causal=True`` masks strictly-future positions.
Fully-masked rows produce zeros (guarded divide), so ragged/padded
sequences are safe. ``window = W`` (causal only) lets position ``i`` see
``j`` with ``i - W < j <= i``: the position itself and the ``W - 1``
before it.

Grouped key/value heads: k and v may carry fewer heads than q, ``H_kv``
dividing ``H``; query head ``h`` then reads key/value head ``h // G``
with ``G = H / H_kv``. No implementation repeats k or v in memory: the
jnp forms contract q as ``(.., H_kv, G, D)``, the kernels' index maps
hand G consecutive query heads the same k/v tile.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def use_interpret(interpret: Optional[bool]) -> bool:
    """The one place a kernel's ``interpret`` flag is decided: ``None``
    means compiled on a TPU backend and the Pallas interpreter on the
    CPU backend (``dev = cpu``, the tests) — the same kernel code runs
    either way."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """One ``out_shape`` entry of a ``pallas_call`` that may sit inside
    a ``shard_map``: under its ``check_vma`` the struct must say over
    which mesh axes the output varies, and a kernel's output varies
    wherever any of its ``operands`` does (the empty set outside a
    shard_map)."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


_NEG = -1e30
#: dot_general dimension numbers of ``a @ b.T`` and ``a.T @ b`` on 2-D
#: tiles: the transposed operand is contracted in place, not copied
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _scale(q: jax.Array, scale: Optional[float]) -> float:
    return (q.shape[-1] ** -0.5) if scale is None else scale


def rope(x: jax.Array, theta: float = 10000.0,
         offset=0) -> jax.Array:
    """Rotary position embedding on (B, S, H, D) (D even): rotates feature
    pairs by position-dependent angles, encoding relative positions
    directly in the q/k dot products. ``offset`` shifts the position base
    (for sequence-sharded shards; may be a traced scalar, e.g.
    lax.axis_index under shard_map)."""
    B, S, H, D = x.shape
    if D % 2:
        raise ValueError(f"rope needs an even head_dim, got {D}")
    pos = jnp.arange(S, dtype=jnp.float32) + jnp.asarray(offset, jnp.float32)
    inv = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    ang = pos[:, None] * inv[None, :]                 # (S, D/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def rope_at(x: jax.Array, theta: float, pos: jax.Array) -> jax.Array:
    """Rotary embedding at explicit per-token positions: ``x`` is
    (B, S, H, D), ``pos`` is an int array (B, S) of absolute positions.
    Element-for-element the same math as :func:`rope` (same ``pos * inv``
    products, same cos/sin combine), so a decode step that rotates one
    token at position ``p`` reproduces bit-for-bit what a full forward
    pass computed for that row — the property the paged KV-cache's
    greedy-decode parity contract rests on. Needed because ``rope``'s
    scalar ``offset`` cannot express a batch of sequences each at a
    different decode position."""
    B, S, H, D = x.shape
    if D % 2:
        raise ValueError(f"rope needs an even head_dim, got {D}")
    p = pos.astype(jnp.float32)
    inv = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    ang = p[:, :, None] * inv[None, None, :]          # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def rope_interleaved(x: jax.Array, theta: float, offset=0) -> jax.Array:
    """Rotary embedding on INTERLEAVED pairs of (B, S, H, D): features
    (2i, 2i+1) are one pair, rotated by ``pos * theta**(-2i/D)`` (the
    ``rope_interleave`` layout of the DeepSeek-V3 family's checkpoints;
    :func:`rope` pairs feature i with i + D/2). The output keeps the
    interleaved order, so q.k is what the published model computes."""
    B, S, H, D = x.shape
    if D % 2:
        raise ValueError(f"rope needs an even head_dim, got {D}")
    pos = jnp.arange(S, dtype=jnp.float32) + jnp.asarray(offset, jnp.float32)
    inv = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    ang = pos[:, None] * inv[None, :]                 # (S, D/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    xp = x.astype(jnp.float32).reshape(B, S, H, D // 2, 2)
    x1, x2 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(B, S, H, D).astype(x.dtype)


def rope_frequencies(rot_dim: int, theta: float, yarn=None):
    """``(the rot_dim / 2 pairs' frequencies as a tuple of floats, the
    factor cos and sin carry)`` of a rotary over ``rot_dim`` features at
    base ``theta``. ``yarn = None`` is the plain table ``f_i =
    theta^(-2i / rot_dim)`` with factor 1. ``yarn = (factor,
    original_max_position, beta_fast, beta_slow, attention_factor)`` is
    YaRN's (arXiv:2309.00071, as transformers computes it): with
    ``dim(n) = rot_dim ln(original / (2 pi n)) / (2 ln theta)``, ``low =
    floor(dim(beta_fast))`` and ``high = ceil(dim(beta_slow))`` (clamped
    to the table), pair ``i`` keeps ``f_i`` below ``low``, takes ``f_i /
    factor`` above ``high`` and the ramp's mix between; cos and sin are
    scaled by ``attention_factor``. Host arithmetic in float64: the table
    is a constant of the layer."""
    half = rot_dim // 2
    f = [theta ** (-2.0 * i / rot_dim) for i in range(half)]
    if yarn is None:
        return tuple(f), 1.0
    factor, original, beta_fast, beta_slow, attention_factor = yarn

    def dim_of(turns):
        return rot_dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), rot_dim - 1)
    if low == high:
        high += 0.001
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
            for i in range(half)]
    return tuple(fi * (1.0 - r) + fi / factor * r
                 for fi, r in zip(f, ramp)), float(attention_factor)


def rope_partial(x: jax.Array, freqs, mscale: float = 1.0,
                 offset=0) -> jax.Array:
    """Rotary on the FIRST ``2 len(freqs)`` features of (B, S, H, D), on
    halves (feature ``i`` of the rotated part pairs with ``i +
    len(freqs)``: ``rotate_half``), pair ``i`` turned by ``pos *
    freqs[i]``, cos and sin times ``mscale``; the other features pass
    through untouched (``partial_rotary_factor``). ``freqs`` is
    :func:`rope_frequencies`'s table. With the whole head rotated, the
    plain table and ``mscale`` 1 this is :func:`rope`."""
    half = len(freqs)
    if 2 * half > x.shape[-1]:
        raise ValueError(f"rope_partial: {2 * half} rotated features in a "
                         f"head of {x.shape[-1]}")
    pos = jnp.arange(x.shape[1], dtype=jnp.float32) \
        + jnp.asarray(offset, jnp.float32)
    ang = pos[:, None] * jnp.asarray(freqs, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * mscale)[None, :, None, :]
    sin = (jnp.sin(ang) * mscale)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., 2 * half:]], axis=-1).astype(x.dtype)


def rope_sections(x: jax.Array, freqs, pos: jax.Array,
                  sections) -> jax.Array:
    """Rotary whose pairs read DIFFERENT position rows (the multimodal
    rotary of the Qwen2-VL family, ``mrope_section``), on halves over the
    first ``2 len(freqs)`` features of (B, S, H, D): ``pos`` is (B, 3, S)
    — a token's temporal, height and width position — and pair ``i``
    turns by ``pos[c(i)] * freqs[i]`` with ``c(i)`` the section ``i``
    falls in, the sections CONTIGUOUS: the first ``sections[0]`` pairs
    read row 0, the next ``sections[1]`` row 1, the rest row 2. With the
    three rows equal to the token's index this is :func:`rope_partial`
    (text)."""
    half = len(freqs)
    if sum(sections) != half or len(sections) != pos.shape[1]:
        raise ValueError(f"rope_sections: sections {tuple(sections)} over "
                         f"{half} pairs and {pos.shape[1]} position rows")
    row = [c for c, n in enumerate(sections) for _ in range(n)]
    p = pos.astype(jnp.float32)[:, jnp.asarray(row), :]     # (B, half, S)
    ang = p.transpose(0, 2, 1) * jnp.asarray(freqs, jnp.float32)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., 2 * half:]], axis=-1).astype(x.dtype)


def _group(q: jax.Array, k: jax.Array) -> int:
    """Query heads per key/value head of (B, S, H, D) / (B, S, H_kv, D)."""
    H, Hkv = q.shape[2], k.shape[2]
    if H % Hkv:
        raise ValueError(f"attention: {H} query heads over {Hkv} key/value "
                         "heads")
    return H // Hkv


def _keep(q_pos, k_pos, window):
    """Causal keep-mask of broadcastable position arrays, inside
    ``window`` where one is given."""
    keep = q_pos >= k_pos
    if window is not None:
        keep = jnp.logical_and(keep, k_pos > q_pos - window)
    return keep


def _check_window(causal, window):
    if window is not None and (not causal or window < 1):
        raise ValueError("attention: a window needs causal = True and at "
                         "least one position")


def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        window: Optional[int] = None,
                        select: Optional[jax.Array] = None) -> jax.Array:
    """Plain softmax attention. q: (B, S, H, D), k,v: (B, S, H_kv, D)
    -> (B, S, H, Dv). ``select`` (B, Sq, Sk), boolean or integer: the
    pairs every head may attend, beside ``causal`` and ``window``."""
    _check_window(causal, window)
    B, Sq, H, D = q.shape
    G = _group(q, k)
    if G == 1:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * _scale(q, scale)
    else:
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(B, Sq, H // G, G, D),
                       k, preferred_element_type=jnp.float32) \
            * _scale(q, scale)
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 2)
        ki = lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
        s = jnp.where(_keep(qi, ki, window), s, _NEG)
    if select is not None:
        s = jnp.where((select != 0).reshape(
            (B,) + (1,) * (s.ndim - 3) + select.shape[1:]), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if G == 1:
        return jnp.einsum("bhqk,bkhd->bqhd", p,
                          v.astype(p.dtype)).astype(q.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(p.dtype))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    tables: jax.Array, q_pos: jax.Array,
                    lengths: jax.Array,
                    scale: Optional[float] = None) -> jax.Array:
    """Attention over a paged KV-cache (vLLM's PagedAttention shape,
    gather-style): each sequence's keys/values live in fixed-size token
    blocks of a shared pool, addressed by a per-sequence block table.

    q:        (B, Q, H, D) query tokens (Q=1 for a decode step, Q=chunk
              for prefill);
    k_pool /
    v_pool:   (N, bs, H, D) — N blocks of bs tokens each (block 0 is the
              caller's scratch block: padding rows write there and the
              masks below never read it as valid);
    tables:   (B, T) int32 — block ids; logical token ``i`` of sequence
              ``b`` lives at ``(tables[b, i // bs], i % bs)``;
    q_pos:    (B, Q) int32 absolute positions of the query tokens;
    lengths:  (B,) int32 valid tokens per sequence (0 = dead row).

    Masking is causal-by-position AND bounded by ``lengths`` (block-tail
    padding), mirroring ``attention_reference``'s -1e30 + softmax
    convention; logits accumulate in fp32 (preferred_element_type), so
    the output matches the reference path to fp32 tolerance. Per-row
    math depends only on that row's q/table/pool content — co-batched
    sequences cannot perturb each other, which is what makes
    iteration-level (continuous) batching bit-identical to the
    request-level path. Returns (B, Q, H, D)."""
    N, bs, H, D = k_pool.shape
    B, T = tables.shape
    kg = k_pool[tables].reshape(B, T * bs, H, D)
    vg = v_pool[tables].reshape(B, T * bs, H, D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kg,
                   preferred_element_type=jnp.float32) * _scale(q, scale)
    # gathered flat index IS the logical token position (ordered tables)
    k_pos = lax.broadcasted_iota(jnp.int32, (B, 1, 1, T * bs), 3)
    mask = (k_pos <= q_pos[:, None, :, None]) \
        & (k_pos < lengths[:, None, None, None])
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      vg.astype(p.dtype)).astype(q.dtype)


def gather_kv_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        axis_name: str, causal: bool = False,
                        scale: Optional[float] = None) -> jax.Array:
    """Sequence-parallel attention via ONE k/v all-gather per projection:
    local q shard attends over the gathered global k/v with global-position
    causal masking. Numerically identical to ring_attention; exists for the
    pipeline-parallel composition, where the ring's collective_permute is
    unsafe inside a stage's switch branch (its rendezvous is global across
    the mesh on the CPU runtime — devices in other stages never arrive)
    while all_gather participation is subgroup-scoped. Costs O(S_global)
    k/v bytes per shard instead of the ring's O(S_local) residency.
    q,k,v: (B, S_local, H, D) -> (B, S_local, H, D)."""
    kg = lax.all_gather(k, axis_name, axis=1, tiled=True)
    vg = lax.all_gather(v, axis_name, axis=1, tiled=True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kg,
                   preferred_element_type=jnp.float32) * _scale(q, scale)
    if causal:
        off = lax.axis_index(axis_name) * q.shape[1]
        qi = lax.broadcasted_iota(jnp.int32, s.shape, 2) + off
        ki = lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(qi >= ki, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      vg.astype(p.dtype)).astype(q.dtype)


def _online_block_update(acc, m, l, q, kb, vb, q_pos, k_pos, scale, causal,
                         k_valid_upto=None, window=None, select=None):
    """One online-softmax accumulation step against key/value block (kb, vb).

    acc: (B,H,Sq,D) f32, m/l: (B,H,Sq) f32; q: (B,Sq,H,D);
    kb/vb: (B,Sk,H,D); q_pos: (Sq,), k_pos: (Sk,) global positions.
    ``k_valid_upto`` masks key positions >= that bound (block tail padding)
    independently of the causal mask; ``window`` narrows the causal mask;
    ``select`` (B, Sq, Sk-block) boolean keeps the selected pairs only.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                   preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        mask = _keep(q_pos[:, None], k_pos[None, :], window)
    if k_valid_upto is not None:
        valid = (k_pos < k_valid_upto)[None, :]
        mask = valid if mask is None else jnp.logical_and(mask, valid)
    if mask is not None:
        mask = mask[None, None]
    if select is not None:
        select = select[:, None]
        mask = select if mask is None else jnp.logical_and(mask, select)
    if mask is not None:
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # exp under the new running max; explicitly zero masked entries so a
    # fully-masked block contributes nothing (avoids exp(-NEG+NEG)=1)
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
    return acc_new, m_new, l_new


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = False, scale: Optional[float] = None,
                      block_k: int = 128,
                      window: Optional[int] = None,
                      select: Optional[jax.Array] = None) -> jax.Array:
    """Online-softmax attention scanning over k/v blocks. q: (B, S, H,
    D), k,v: (B, S, H_kv, D). Grouped heads ride the query axis: the G
    query heads of a key/value head are G queries at the same position,
    so one scan over k and v as they stand serves them all. ``select``
    (B, Sq, Sk): the pairs every head may attend, a block of keys at a
    time."""
    _check_window(causal, window)
    B, Sq, H, D = q.shape
    G = _group(q, k)
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    sc = _scale(q, scale)
    q_pos = jnp.arange(Sq)
    if G > 1:
        q = q.reshape(B, Sq, Hkv, G, D).transpose(0, 1, 3, 2, 4) \
            .reshape(B, Sq * G, Hkv, D)
        q_pos = jnp.repeat(q_pos, G)
        if select is not None:
            select = jnp.repeat(select, G, axis=1)
    block_k = min(block_k, Sk)
    nb = -(-Sk // block_k)
    pad = nb * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nb, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block_k, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    blocks = (jnp.arange(nb), kb, vb)
    if select is not None:
        sel = jnp.pad(select != 0, ((0, 0), (0, 0), (0, pad)))
        blocks += (sel.reshape(B, -1, nb, block_k).transpose(2, 0, 1, 3),)

    def step(carry, blk):
        acc, m, l = carry
        j, kj, vj, *sj = blk
        k_pos = j * block_k + jnp.arange(block_k)
        acc, m, l = _online_block_update(
            acc, m, l, q, kj, vj, q_pos, k_pos, sc, causal,
            k_valid_upto=Sk if pad else None, window=window,
            select=sj[0] if sj else None)
        return (acc, m, l), None

    acc0 = jnp.zeros((B, Hkv, Sq * G, Dv), jnp.float32)
    m0 = jnp.full((B, Hkv, Sq * G), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, Sq * G), jnp.float32)
    (acc, m, l), _ = lax.scan(step, (acc0, m0, l0), blocks)
    out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B, Hkv, Sq G, Dv)
    out = out.reshape(B, Hkv, Sq, G, Dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Sq, H, Dv).astype(q.dtype)


# -- Pallas flash attention ---------------------------------------------------

def _tile_mask(off, rows, cols, window=None, transposed=False):
    """Causal keep-mask of a (rows, cols) tile of scores whose first
    query stands ``off`` positions after its first key (the mask reads
    only the distance between a query and a key) — shared by the forward
    and the backward kernel so the masking convention cannot drift
    between them. ``off`` is a Python int for a sub-tile of the kernels'
    table and a traced scalar for a whole unsquare tile. ``transposed``:
    the (cols, rows) tile the backward holds. ``window`` narrows it to
    the band."""
    shape = (cols, rows) if transposed else (rows, cols)
    qpos = off + lax.broadcasted_iota(
        jnp.int32, shape, 1 if transposed else 0)
    kpos = lax.broadcasted_iota(jnp.int32, shape, 0 if transposed else 1)
    return _keep(qpos, kpos, window)


def _at_least_0(x):
    """``max(x, 0)`` of a Python int or a traced one alike (the bands
    below are taken by the wrappers in Python and by the kernels and
    their index maps on the device)."""
    return x * (x > 0)


def _band_k(qi, block_q, block_k, window):
    """``(first, last)`` k-block the band of q-block ``qi`` touches."""
    return (_at_least_0(qi * block_q - (window - 1)) // block_k,
            (qi * block_q + block_q - 1) // block_k)


def _band_q(kj, block_q, block_k, window, nq):
    """``(first, last)`` q-block whose band touches k-block ``kj``."""
    last = (kj * block_k + block_k - 1 + window - 1) // block_q
    return (kj * block_k) // block_q, nq - 1 - _at_least_0(nq - 1 - last)


def flash_tiles(positions: int, block: int, window: Optional[int] = None):
    """``(score tiles the causal kernels execute, tiles of the square)``
    for one head at square blocks of ``block``, forward (the backward
    executes the same tiles): the tiles at or below the diagonal, inside
    the band where there is a window."""
    n = positions // block
    if window is None:
        return n * (n + 1) // 2, n * n
    done = 0
    for i in range(n):
        first, last = _band_k(i, block, block, window)
        done += last - first + 1
    return done, n * n


#: the smallest square an edge tile is taken apart into (PERF.md section
#: 6, PR 33: on the chip sub-tiles of 128 lost to the whole tile at
#: every shape swept, and 256 under blocks of 512 lost too)
_MIN_SUBTILE = 256


def _subtile(block: int) -> int:
    """The side of the square sub-tiles the kernels take an EDGE tile by
    (a tile the causal diagonal or a band's trailing edge crosses), from
    the block alone: a quarter of the block where that is at least
    ``_MIN_SUBTILE`` (and whole lane tiles), else the block — the tile
    is then its own one sub-tile and is masked whole. Blocks of 1024 go
    by 256s (10 of an edge tile's 16 sub-tiles multiplied); blocks of
    512 and less are not taken apart."""
    quarter = block // 4
    if block % 4 == 0 and quarter >= _MIN_SUBTILE and quarter % 128 == 0:
        return quarter
    return block


def _pairs_kept(off: int, side: int, window: Optional[int]) -> str:
    """What the causal mask keeps of a ``side`` x ``side`` square of
    scores whose first query stands ``off`` positions after its first
    key: ``"all"``, ``"none"`` or ``"some"`` of its pairs. Python ints:
    the kernels' tables are made of it while they are traced."""
    nearest, farthest = off - (side - 1), off + (side - 1)   # of q - k
    if farthest < 0 or (window is not None and nearest >= window):
        return "none"
    if nearest >= 0 and (window is None or farthest < window):
        return "all"
    return "some"


def _edge_tiles(block: int, window: Optional[int]) -> dict:
    """The kernels' table at square blocks: ``{offset: q sub-rows}`` for
    every offset ``q-block's first position - k-block's`` at which an
    edge crosses the tile (0, the diagonal; the one or two multiples of
    the block at which a band's trailing edge falls). A q sub-row is
    ``(a, parts)`` in units of :func:`_subtile`: the sub-tiles of row
    ``a`` that keep a pair, in order, as ``(first k sub-column, how
    many, mask)`` — neighbours that keep every pair are one part with
    ``mask`` ``None``, a sub-tile an edge crosses is a part of its own
    with its offset as ``mask``, for :func:`_tile_mask`; sub-tiles that
    keep no pair are in no part, and a row of none is left out. An
    executed tile at any other offset keeps every pair."""
    sub = _subtile(block)
    n = block // sub
    table = {}
    for off in range(0, block if window is None else window + block, block):
        if _pairs_kept(off, block, window) != "some":
            continue
        rows = []
        for a in range(n):
            parts = []
            for b in range(n):
                e = off + (a - b) * sub
                kept = _pairs_kept(e, sub, window)
                if kept == "some":
                    parts.append((b, 1, e))
                elif kept == "all" and parts and parts[-1][2] is None \
                        and sum(parts[-1][:2]) == b:
                    parts[-1] = (parts[-1][0], parts[-1][1] + 1, None)
                elif kept == "all":
                    parts.append((b, 1, None))
            if parts:
                rows.append((a, tuple(parts)))
        table[off] = tuple(rows)
    return table


def flash_tile_classes(positions: int, block: int,
                       window: Optional[int] = None) -> dict:
    """What one head of the causal kernels does at square blocks of
    ``block``, forward (the backward does the same), by class of tile:

    * ``interior``: executed tiles that keep every pair — multiplied
      whole, no mask built;
    * ``edge``: executed tiles an edge crosses, taken by sub-tiles of
      ``subtile`` (:func:`_subtile`): ``sub_plain`` of those keep every
      pair (no mask), ``sub_masked`` are crossed by an edge (the only
      masks built), ``sub_skipped`` keep no pair and are not multiplied;
    * ``pairs_multiplied``: score pairs that reach the MXU (interior
      tiles whole, the edge tiles' sub-tiles but the skipped), against
      ``pairs_attended``, the pairs the mask keeps.

    ``interior + edge`` is :func:`flash_tiles`'s first count."""
    n, sub = positions // block, _subtile(block)
    table = _edge_tiles(block, window)
    out = dict.fromkeys(("interior", "edge", "sub_plain", "sub_masked",
                         "sub_skipped"), 0)
    for i in range(n):
        first = 0 if window is None else _band_k(i, block, block, window)[0]
        for j in range(first, i + 1):
            subs = table.get((i - j) * block)
            if subs is None:
                out["interior"] += 1
                continue
            parts = [part for _, parts in subs for part in parts]
            masked = sum(1 for _, _, mask in parts if mask is not None)
            plain = sum(n for _, n, mask in parts if mask is None)
            out["edge"] += 1
            out["sub_masked"] += masked
            out["sub_plain"] += plain
            out["sub_skipped"] += (block // sub) ** 2 - masked - plain
    w = positions if window is None else min(window, positions)
    out["subtile"] = sub
    out["pairs_multiplied"] = out["interior"] * block * block + (
        out["sub_plain"] + out["sub_masked"]) * sub * sub
    out["pairs_attended"] = w * (w + 1) // 2 + (positions - w) * w
    return out


def _kept_parts(body, run, off, block_q, block_k, causal, window,
                transposed=False):
    """Both kernels' one way through a tile: ``body(rows, parts)`` runs
    for each stretch ``rows`` of the q-block that keeps a pair, with the
    ``parts`` of the k-block it keeps them in, in order, as ``(cols,
    mask)`` — ``rows`` and ``cols`` ``pl.ds`` slices, ``mask`` ``None``
    where every pair of the part is kept and else its keep-mask. ``run``
    says the cell's tile is one the kernel executes (at or below the
    diagonal, inside the band), ``off`` is ``q-block's first position -
    k-block's``, both traced.

    At square blocks the tile is classified by ``off``: an interior tile
    is one part with no mask; an edge tile goes by the q sub-rows of
    :func:`_edge_tiles`, whose sub-tiles without a kept pair are in no
    part and of which only those an edge crosses carry a mask, of the
    sub-tile's shape. Blocks that are not square are one masked part,
    whatever ``off``."""
    whole_q, whole_k = pl.ds(0, block_q), pl.ds(0, block_k)
    if not causal:
        body(whole_q, ((whole_k, None),))
        return
    if block_q != block_k:
        @pl.when(run)
        def _whole():
            body(whole_q, ((whole_k, _tile_mask(
                off, block_q, block_k, window, transposed)),))
        return
    sub = _subtile(block_q)
    interior = off >= block_q - 1
    if window is not None:
        interior = jnp.logical_and(interior, off + block_q <= window)
    if window is None or any(_pairs_kept(d, block_q, window) == "all"
                             for d in range(0, window, block_q)):

        @pl.when(jnp.logical_and(run, interior))
        def _interior():
            body(whole_q, ((whole_k, None),))

    for edge_off, sub_rows in _edge_tiles(block_q, window).items():

        @pl.when(jnp.logical_and(run, off == edge_off))
        def _edge(sub_rows=sub_rows):
            for a, parts in sub_rows:
                body(pl.ds(a * sub, sub), tuple(
                    (pl.ds(b * sub, n * sub),
                     None if mask is None else _tile_mask(
                         mask, sub, sub, window, transposed))
                    for b, n, mask in parts))


def _fwd_attend(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale):
    """The forward kernels' one step of the online softmax: ``attend(rows,
    parts)`` takes the rows' scores against the ``parts`` of the k-block
    (``(cols, mask)``, ``mask`` ``None`` where every pair is kept)
    together."""
    def attend(rows, parts):
        # operands go to the MXU in their own dtype (a bf16 product
        # upcast to float32 first costs several passes), sums in float32
        q = q_ref[0, rows, :]                     # (rows, D)
        s = []
        for cols, mask in parts:                  # kb: (cols, D)
            sc = lax.dot_general(q, k_ref[0, cols, :], _NT,
                                 preferred_element_type=jnp.float32) * scale
            s.append(sc if mask is None else jnp.where(mask, sc, _NEG))
        # ONE step of the online softmax over the row's parts together:
        # the running statistics are (rows, 1) columns, and each pass
        # over them costs as much as a pass over 128 columns of scores
        m_prev = m_ref[rows, 0]
        m_new = m_prev
        for sc in s:
            m_new = jnp.maximum(m_new, jnp.max(sc, axis=-1))
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[rows, 0] * corr
        acc = acc_ref[rows, :] * corr[:, None]
        for (cols, mask), sc in zip(parts, s):
            p = jnp.exp(sc - m_new[:, None])
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            l_new = l_new + jnp.sum(p, axis=-1)
            vb = v_ref[0, cols, :]                # (cols, Dv)
            acc = acc + jnp.dot(p.astype(vb.dtype), vb,
                                preferred_element_type=jnp.float32)
        l_ref[rows, 0] = l_new
        acc_ref[rows, :] = acc
        m_ref[rows, 0] = m_new
    return attend


def _fwd_finish(o_ref, lse_ref, acc_ref, m_ref, l_ref):
    l_fin = jnp.maximum(l_ref[:, 0], 1e-30)
    o_ref[0] = (acc_ref[...] / l_fin[:, None]).astype(o_ref.dtype)
    lse_ref[0, :, 0] = m_ref[:, 0] + jnp.log(l_fin)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *,
                      scale, causal, block_q, block_k, window=None):
    """One (batch*head, q-block, k-block) grid cell. K/V truly stream: each
    cell sees only one (block_k, D) K/V tile in VMEM; the online-softmax
    accumulators persist in VMEM scratch across the (innermost, sequential)
    k-block grid dimension, so VMEM residency is O(block) not O(S).
    Also emits the per-row logsumexp — the statistic the fused backward
    kernels rebuild the softmax from without a second online pass.
    With a ``window`` the innermost dimension runs over the k-blocks of
    the q-block's band only (``_band_k``): the cell's k-block is the
    band's first plus the grid index, and cells past the band's last
    (the count is the widest band's) do nothing and fetch nothing new.
    A causal tile goes by what it keeps (:func:`_kept_parts`): whole
    and without a mask where every pair is kept, else by its q sub-rows,
    each taking the parts it keeps together in one step of the online
    softmax.
    """
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    if window is not None:
        first, last = _band_k(qi, block_q, block_k, window)
        kb_idx = first + kj
    else:
        kb_idx = kj

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    attend = _fwd_attend(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale)

    # tiles past the band's last block, or strictly above the causal
    # diagonal, are skipped; an executed tile goes by what it keeps
    run = kb_idx <= last if window is not None else \
        kj * block_k <= qi * block_q + block_q - 1
    _kept_parts(attend, run, qi * block_q - kb_idx * block_k,
                block_q, block_k, causal, window)

    @pl.when(kj == nk - 1)
    def _finish():
        _fwd_finish(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _heads_flat(a):
    """(B, S, H, D) -> (B*H, S, D): one grid row per (batch, head)."""
    B, S, H, D = a.shape
    return a.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _blocks(Sq, Sk, block_q, block_k, causal, window):
    _check_window(causal, window)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"flash_attention: seq lengths ({Sq},{Sk}) must be divisible by "
            f"blocks ({block_q},{block_k})")
    if window is not None and Sq != Sk:
        raise ValueError("flash_attention: a window needs as many keys as "
                         "queries")
    return block_q, block_k


# The two calls of the kernels are jitted and inlined: a kernel's body
# is then traced once per shape and set of options, not once per layer
# and differentiation pass (every layer of a net calls with the same,
# and a step traces a layer's forward twice); inlined, the call leaves
# no trace of its own in the step, and run alone it is the one
# computation a ``pallas_call`` always was.
@functools.partial(jax.jit, static_argnums=tuple(range(3, 10)), inline=True)
def _forward_call(qt, kt, vt, G, scale, causal, block_q, block_k, interpret,
                  window):
    """``flash_fwd`` on (B*H, S, D) rows, ``G`` consecutive query rows
    reading one key/value row: ``(out, logsumexp (B*H, Sq, 1))``."""
    (BH, Sq, D), Sk, Dv = qt.shape, kt.shape[1], vt.shape[-1]
    nq, nk = Sq // block_q, Sk // block_k
    kern = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, window=window)
    # G consecutive query heads (grid rows) read one key/value head
    kv_row = (lambda b: b) if G == 1 else (lambda b: b // G)
    if window is None:
        kv_map = lambda b, i, j: (kv_row(b), j, 0)
    else:
        # the innermost dimension counts the k-blocks of a band, the
        # widest's many; past a band's last block the index stands still
        nk = max(last - first + 1 for first, last in (
            _band_k(i, block_q, block_k, window) for i in range(nq)))

        def kv_map(b, i, j):
            first, last = _band_k(i, block_q, block_k, window)
            return kv_row(b), jnp.minimum(first + j, last), 0
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, Dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            # (BH, Sq, 1): trailing dims (block_q, 1) satisfy the TPU
            # (8, 128)-divisible-or-full block constraint
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            out_struct((BH, Sq, Dv), qt.dtype, qt, kt, vt),
            out_struct((BH, Sq, 1), jnp.float32, qt, kt, vt),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),  # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running normalizer
        ],
        interpret=interpret, name="flash_fwd",
    )(qt, kt, vt)


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   with_lse: bool = False, window=None):
    B, Sq, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    block_q, block_k = _blocks(Sq, Sk, block_q, block_k, causal, window)
    out, lse = _forward_call(
        _heads_flat(q), _heads_flat(k), _heads_flat(v), _group(q, k),
        _scale(q, scale), causal, block_q, block_k, interpret, window)
    out = out.reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)
    return (out, lse) if with_lse else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention (B,S,H,D): Pallas forward, fused Pallas backward.
    v may be narrower or wider than q and k; k and v may carry fewer
    heads than q (grouped: query head ``h`` reads key/value head ``h //
    G``, from k and v as they stand); ``window`` (causal only) keeps the
    ``window`` positions up to and including the query's own, and tiles
    wholly outside the band are neither computed nor fetched. With as
    many key/value heads as query heads and no window the kernels are
    the plain causal ones. The backward is one kernel
    (``flash_bwd``, :func:`_flash_backward`) at the forward's blocks: it
    needs q, k, v, the cotangent, and the forward's output and
    logsumexp, which are saved under the names ``FLASH_RESIDUALS`` — a
    rematerialising caller keeps those two and rebuilds the rest.

    ``interpret=None``: compiled on a TPU backend, interpreted on the
    CPU backend — the same kernel is exercised in CPU tests (the
    pairtest spirit, SURVEY §4).
    """
    return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          use_interpret(interpret), window=window)


def _bwd_part_grads(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, dk_acc, dv_acc, scale, q_first):
    """The backward kernels' work on one part of a tile, held
    transposed: ``grads(rows, parts)`` rebuilds each part's P from the
    saved logsumexp and adds to its rows of dV, dK and dQ. ``q_first``
    is the q-block's first position in the head's resident dq row."""
    def part_grads(rows, cols, mask):
        q = q_ref[0, rows, :]                     # (rows, D)
        kb = k_ref[0, cols, :]                    # (cols, D)
        vb = v_ref[0, cols, :]                    # (cols, Dv)
        do = do_ref[0, rows, :]                   # (rows, Dv)
        st = lax.dot_general(kb, q, _NT,
                             preferred_element_type=jnp.float32) * scale
        pt = jnp.exp(st - lse_ref[0, :, rows])    # lse: (1, rows)
        if mask is not None:
            # explicit zeroing: fully-masked rows carry a sentinel lse,
            # where exp(s - lse) would NOT vanish on its own
            pt = jnp.where(mask, pt, 0.0)
        dv_acc[cols, :] += jnp.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dpt = lax.dot_general(vb, do, _NT,
                              preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[0, :, rows]) * scale).astype(q.dtype)
        dk_acc[cols, :] += jnp.dot(dst, q,
                                   preferred_element_type=jnp.float32)
        at = pl.ds(pl.multiple_of(q_first + rows.start, rows.size),
                   rows.size)
        dq_ref[0, at, :] += lax.dot_general(
            dst, kb, _TN, preferred_element_type=jnp.float32)

    def grads(rows, parts):
        for cols, mask in parts:
            part_grads(rows, cols, mask)
    return grads


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      scale, causal, block_q, block_k, window=None):
    """The whole backward of one (batch*head, k-block, q-block) grid cell:
    the score tile, its softmax P (rebuilt from the saved logsumexp, no
    second online pass), dP and dS = P o (dP - delta) are made ONCE and
    feed all three gradients,

      dV_j += P_ij^T dO_i    dK_j += dS_ij^T Q_i    dQ_i += dS_ij K_j

    (dS carries ``scale``). The tile is held TRANSPOSED, (block_k,
    block_q): dV's and dK's products then contract its lanes as they
    stand and only dQ's contracts its rows, and the per-row statistics
    arrive lane-dense as (1, block_q) rows. The q-blocks are the
    innermost, sequential grid dimension: dK and dV of the k-block
    accumulate in VMEM scratch across it; dQ is the float32 block of the
    head's WHOLE row, resident in VMEM while the head's tiles run (its
    index map is constant within a head), added to in place at the
    q-block's rows and written back once a head. With a ``window`` the
    innermost dimension runs over the q-blocks whose band touches the
    k-block only (``_band_q``), as the forward's runs over a band's
    k-blocks. A causal tile goes by what it keeps, as the forward's
    (:func:`_kept_parts`): each part rebuilds its own P and adds to its
    own rows of dV, dK and dQ. dK and dV are a QUERY head's: where heads
    are grouped the caller sums a group's."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    if window is not None:
        first, last = _band_q(kj, block_q, block_k, window,
                              dq_ref.shape[1] // block_q)
        qb_idx = first + qi
    else:
        qb_idx = qi

    @pl.when(jnp.logical_and(kj == 0, qi == 0))
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    grads = _bwd_part_grads(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_acc, dv_acc, scale, qb_idx * block_q)

    # only q blocks inside the k-block's band, or at or below the
    # diagonal, contribute to this k tile
    run = qb_idx <= last if window is not None else \
        qi * block_q + block_q - 1 >= kj * block_k
    _kept_parts(grads, run, qb_idx * block_q - kj * block_k,
                block_q, block_k, causal, window, transposed=True)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


#: what the backward kernel may use of a v5e's 128 MiB of VMEM (the
#: compiler's default scoped limit is 16 MiB, under one head's dq row at
#: 8192 positions): the rest stays the compiler's own
_BWD_VMEM_LIMIT = 96 * 2 ** 20


@functools.partial(jax.jit, static_argnums=tuple(range(6, 13)), inline=True)
def _backward_call(qt, kt, vt, dot, lse, delta, G, scale, causal, block_q,
                   block_k, interpret, window):
    """``flash_bwd`` on (B*H, S, D) rows (``lse`` and ``delta`` lane-dense,
    (B*H, 1, Sq)): ``(dq in float32, dk, dv)``, dk and dv a QUERY row's
    — in float32 where ``G`` of them share a key/value row."""
    (BH, Sq, D), Sk, Dv = qt.shape, kt.shape[1], vt.shape[-1]
    nq, nk = Sq // block_q, Sk // block_k
    # grid (bh, k-block j, q-block i), the q-blocks innermost
    kv_row = (lambda b: b) if G == 1 else (lambda b: b // G)
    if window is None:
        q_blk = lambda j, i: i
    else:
        nq = max(last - first + 1 for first, last in (
            _band_q(j, block_q, block_k, window, nq) for j in range(nk)))

        def q_blk(j, i, _nq=Sq // block_q):
            first, last = _band_q(j, block_q, block_k, window, _nq)
            return jnp.minimum(first + i, last)
    q_spec = pl.BlockSpec((1, block_q, D),
                          lambda b, j, i: (b, q_blk(j, i), 0))
    k_spec = pl.BlockSpec((1, block_k, D),
                          lambda b, j, i: (kv_row(b), j, 0))
    v_spec = pl.BlockSpec((1, block_k, Dv),
                          lambda b, j, i: (kv_row(b), j, 0))
    o_spec = pl.BlockSpec((1, block_q, Dv),
                          lambda b, j, i: (b, q_blk(j, i), 0))
    r_spec = pl.BlockSpec((1, 1, block_q),
                          lambda b, j, i: (b, 0, q_blk(j, i)))
    dq_spec = pl.BlockSpec((1, Sq, D), lambda b, j, i: (b, 0, 0))
    dk_spec = pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))
    dv_spec = pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0))
    part = (lambda a: a.dtype) if G == 1 else (lambda a: jnp.float32)
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, window=window),
        grid=(BH, nk, nq),
        in_specs=[q_spec, k_spec, v_spec, o_spec, r_spec, r_spec],
        out_specs=[dq_spec, dk_spec, dv_spec],
        out_shape=[out_struct((BH, Sq, D), jnp.float32, qt, kt, vt, dot),
                   out_struct((BH, Sk, D), part(kt), qt, kt, vt, dot),
                   out_struct((BH, Sk, Dv), part(vt), qt, kt, vt, dot)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret, name="flash_bwd",
    )(qt, kt, vt, dot, lse, delta)


def _flash_backward(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                    interpret, window=None):
    """Fused Pallas backward, ONE kernel (``flash_bwd``): every executed
    tile rebuilds its softmax once from the forward's logsumexp and
    feeds dq, dk and dv from it. ``out`` is (B, Sq, H, Dv) as the
    forward returned it, ``lse`` the lane-dense (B*H, Sq) the forward
    rule holds; dq leaves the kernel in float32 (it is summed in place
    across the k-blocks) and is rounded on the way back to (B, Sq, H, D).
    Where G query heads share a key/value head the kernel's dk and dv
    are a query head's, in float32, and the group's are summed here
    (one pass over them, under a millisecond a layer at the cell's
    shapes; PERF.md section 6, PR 32).
    """
    block_q, block_k = _bwd_blocks(q, k, block_q, block_k, causal, window)
    dq, dk, dv = _backward_call(
        *_bwd_operands(q, k, v, out, lse, g), _group(q, k),
        _scale(q, scale), causal, block_q, block_k, interpret, window)
    return _bwd_results(dq, dk, dv, q, k, v)


def _bwd_blocks(q, k, block_q, block_k, causal, window):
    """The backward kernels' blocks, checked against what they may hold
    in VMEM: a head's float32 dq row (lanes padded to 128s, the output's
    two pipeline buffers) beside the tile's float32 intermediates."""
    Sq, D = q.shape[1], q.shape[3]
    block_q, block_k = _blocks(Sq, k.shape[1], block_q, block_k, causal,
                               window)
    lanes = -(-D // 128) * 128
    need = 2 * Sq * lanes * 4 + 8 * block_q * block_k * 4
    if need > _BWD_VMEM_LIMIT:
        raise ValueError(
            f"flash_attention backward: a head's float32 dq row "
            f"({Sq} x {lanes} lanes, twice) and its ({block_q},{block_k}) "
            f"tiles need {need} bytes of VMEM, over the {_BWD_VMEM_LIMIT} "
            f"the kernel may use: shorten the sequence or shard it")
    return block_q, block_k


def _bwd_operands(q, k, v, out, lse, g):
    """What both backward kernels read, as they read it: q, k, v and the
    cotangent a row a head, the logsumexp and ``delta_i = rowsum(dO_i *
    O_i)`` lane-dense (B*H, 1, Sq)."""
    B, Sq, H, _ = q.shape
    # delta: elementwise where both already lie, then one small
    # transpose to the kernel's lane-dense rows
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1).reshape(B * H, 1, Sq)
    return (*(_heads_flat(a) for a in (q, k, v, g)),
            lse.reshape(B * H, 1, Sq), delta)


def _bwd_results(dq, dk, dv, q, k, v):
    """The backward kernels' (B*H, S, D) outputs as the gradients of q,
    k and v: dq rounded from float32, and where G query heads share a
    key/value head their float32 dk and dv summed."""
    B, Sq, Sk, Hkv = q.shape[0], q.shape[1], k.shape[1], k.shape[2]
    unflat = lambda a, S: a.reshape(B, -1, S, a.shape[-1]).transpose(
        0, 2, 1, 3)
    if _group(q, k) > 1:
        # a key/value head's gradient: the sum over its G query heads
        dk, dv = (jnp.sum(a.reshape(B * Hkv, -1, Sk, a.shape[-1]),
                          axis=1).astype(like.dtype)
                  for a, like in ((dk, k), (dv, v)))
    return unflat(dq.astype(q.dtype), Sq), unflat(dk, Sk), unflat(dv, Sk)


#: the two residuals of the kernel's forward that its backward cannot
#: rebuild cheaply, by the names ``jax.checkpoint`` policies know them
#: under (``model.py`` saves exactly these across ``remat = 1``)
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret,
                    window):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              use_interpret(interpret), with_lse=True,
                              window=window)
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    # held lane-dense, (B*H, Sq): a trailing 1 may be padded to 128 lanes
    lse = checkpoint_name(lse.reshape(lse.shape[:2]), FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, window,
                    res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, scale,
                           block_q, block_k, use_interpret(interpret),
                           window=window)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# -- attention over keys a learned scorer picks -------------------------------
#
# DeepSeek sparse attention (arXiv:2512.02556, section 2.1) in its training
# form: an indexer scores every causal pair, each query keeps its ``topk``
# best, and the main attention runs over the kept pairs alone. The pieces:
# the indexer's score (``index_scores``), the exact selection
# (``select_rows``), the kernels above under a selection operand
# (``flash_attention_select``), and the head-summed attention distribution
# over the selected set that the indexer is trained on (``head_sum_probs``).
# Each is a kernel with an XLA form beside it, its oracle and the ``ref``
# path (``select_topk_reference``: XLA's counting loops); the score and
# the attention each have ONE
# backward kernel (``index_scores_bwd``, ``flash_bwd_select``; the first
# also alone, ``index_scores_backward``, for the layer that runs it in
# the forward pass), the selection and the distribution no derivative.


def _index_scores_rows(qi, ki, w):
    """``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])`` for a stretch
    of queries: qi (B, T, J, d), ki (B, S, d), w (B, T, J) float32 ->
    (B, T, S) float32. The products run in the operands' dtype with
    float32 sums; the relu, the weights and the sum over the indexer's
    heads are float32."""
    s = jnp.einsum("btjd,bsd->bjts", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w.transpose(0, 2, 1)[..., None], axis=1)


def index_scores_reference(qi: jax.Array, ki: jax.Array, w: jax.Array,
                           chunk: int = 512) -> jax.Array:
    """The indexer's scores of every pair, (B, S, S) float32, on XLA's
    dots, ``chunk`` queries at a time where that divides the positions
    (the products of all the indexer's heads against all keys are held a
    chunk at a time, never positions x positions x heads). Differentiable
    by XLA, a chunk at a time under ``jax.checkpoint``: the ``ref`` path
    and the oracle the kernels, forward and backward, are tested
    against."""
    B, S = qi.shape[:2]
    if S <= chunk or S % chunk:
        return _index_scores_rows(qi, ki, w)
    n = S // chunk
    rows = jax.checkpoint(lambda a: _index_scores_rows(a[0], ki, a[1]))
    out = lax.map(rows, (
        qi.reshape(B, n, chunk, *qi.shape[2:]).swapaxes(0, 1),
        w.reshape(B, n, chunk, w.shape[-1]).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(B, S, ki.shape[1])


def _index_scores_kernel(q_ref, k_ref, w_ref, o_ref, *, block):
    """One (batch, q-block, k-block) tile of the indexer's scores: the
    heads' products one after another on the MXU, each through its relu
    and the query's weight into the float32 tile. A tile above the
    diagonal is filled with the mask's value and multiplies nothing."""
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj > qi)
    def _above():
        o_ref[0] = jnp.full(o_ref.shape[1:], _NEG, jnp.float32)

    @pl.when(kj <= qi)
    def _tile():
        kb = k_ref[0]                               # (block, d)
        wb = w_ref[0]                               # (block, J) float32
        acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for j in range(q_ref.shape[1]):
            sc = lax.dot_general(q_ref[0, j], kb, _NT,
                                 preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(sc, 0.0) * wb[:, j:j + 1]
        o_ref[0] = acc


@functools.partial(jax.jit, static_argnums=(3, 4), inline=True)
def _index_scores_call(qi, ki, w, block, interpret):
    B, S, J, d = qi.shape
    n = S // block
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, block=block),
        grid=(B, n, n),
        in_specs=[
            pl.BlockSpec((1, J, block, d), lambda b, i, j: (b, 0, i, 0)),
            # a tile above the diagonal fetches nothing new
            pl.BlockSpec((1, block, d),
                         lambda b, i, j: (b, jnp.minimum(i, j), 0)),
            pl.BlockSpec((1, block, J), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, block), lambda b, i, j: (b, i, j)),
        out_shape=out_struct((B, S, S), jnp.float32, qi, ki, w),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="index_scores",
    )(qi.transpose(0, 2, 1, 3), ki, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def index_scores(qi: jax.Array, ki: jax.Array, w: jax.Array,
                 block: int = 512,
                 interpret: Optional[bool] = None) -> jax.Array:
    """The indexer's scores as a Pallas kernel (``index_scores``), causal:
    qi (B, S, J, d), ki (B, S, d), w (B, S, J) float32 -> (B, S, S)
    float32, pairs above the diagonal at the mask's value. ``block``
    divides the positions. The backward is one kernel too
    (``index_scores_bwd``, :func:`_index_scores_bwd_kernel`) at the
    forward's block; it needs qi, ki, w and the cotangent, nothing of the
    forward's output."""
    return _index_scores_call(qi, ki, w, block, use_interpret(interpret))


def _index_scores_bwd_kernel(q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref,
                             dw_ref, dq_acc, dw_acc, *, block):
    """The whole backward of one (batch, q-block, k-block) tile of the
    indexer's scores: per head the product q_j . k is made ONCE, into a
    float32 tile, and through the relu's mask feeds all three gradients,

      a_j = g o w_j o [q_j . k > 0]
      dq_j += a_j K    dK += a_j^T Q_j    dw_j += rowsum(g o relu(q_j . k))

    The tile is held TRANSPOSED, (block k, block q), as ``flash_bwd``
    holds its own: dK's product contracts its lanes as they stand and
    only dq's contracts its rows, the query's weight arrives lane-dense
    as a (1, block) row and dw's sum runs down the sublanes; the
    cotangent's tile is turned once a tile, for all heads. The k-blocks
    are the innermost, sequential grid dimension: a q-block's dq and dw
    accumulate in VMEM scratch across it and are written once; dK — ONE
    key head — is the float32 block of the batch row's WHOLE length,
    resident in VMEM while the row's tiles run (its index map is
    constant within a row), added to in place once a tile. A tile above
    the diagonal is neither fetched nor multiplied (the forward fills it
    with a constant); a tile on the diagonal zeroes the cotangent above
    it, whatever the caller left there, and goes by what it keeps
    (:func:`_kept_parts`). ``a_j`` is rounded to the operands' dtype
    before its two products, as ``flash_bwd`` rounds dS; the relu, the
    weights, dw and every sum are float32."""
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(qi == 0, kj == 0))
    def _init_dk():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    def part_grads(rows, cols, mask):
        kb = k_ref[0, cols, :]                      # (cols, d)
        g = g_ref[0, rows, cols]                    # (rows, cols) float32
        if mask is not None:
            # before the turn: the chip's compiler folds the mask's
            # constant corners away, and a turned piece left without a
            # use is refused
            g = jnp.where(mask, g, 0.0)
        gt = g.T
        dk = 0.0
        for j in range(q_ref.shape[1]):
            q = q_ref[0, j, rows, :]                # (rows, d)
            st = lax.dot_general(kb, q, _NT,
                                 preferred_element_type=jnp.float32)
            ht = jnp.where(st > 0.0, gt, 0.0)
            at = (ht * w_ref[0, pl.ds(j, 1), rows]).astype(q.dtype)
            dk += jnp.dot(at, q, preferred_element_type=jnp.float32)
            dq_acc[j, rows, :] += lax.dot_general(
                at, kb, _TN, preferred_element_type=jnp.float32)
            dw_acc[pl.ds(j, 1), rows] += jnp.sum(ht * st, axis=0,
                                                 keepdims=True)
        at_k = pl.ds(pl.multiple_of(kj * block + cols.start,
                                    math.gcd(block, cols.start)), cols.size)
        dk_ref[0, at_k, :] += dk

    def grads(rows, parts):
        for cols, mask in parts:
            part_grads(rows, cols, mask)

    _kept_parts(grads, kj <= qi, (qi - kj) * block, block, block, True, None)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_acc[...]


@functools.partial(jax.jit, static_argnums=(4, 5), inline=True)
def _index_scores_bwd_call(qi, ki, w, g, block, interpret):
    """``index_scores_bwd``: the gradients of qi, ki and w, each in its
    own dtype, from the float32 cotangent ``g`` (B, S, S)."""
    B, S, J, d = qi.shape
    n = S // block
    lanes = -(-d // 128) * 128
    # dK's row twice (the output's two pipeline buffers), a q-block's
    # operand, result (two buffers each) and float32 sum, the
    # cotangent's tile twice and the tile's float32 intermediates
    need = 2 * S * lanes * 4 + J * block * lanes * (
        4 * qi.dtype.itemsize + 4) + 8 * block * block * 4
    if need > _BWD_VMEM_LIMIT:
        raise ValueError(
            f"index_scores backward: the key head's float32 gradient row "
            f"({S} x {lanes} lanes, twice), {J} heads' blocks of {block} "
            f"and their tiles need {need} bytes of VMEM, over the "
            f"{_BWD_VMEM_LIMIT} the kernel may use: shorten the sequence "
            f"or shard it")
    q_spec = pl.BlockSpec((1, J, block, d), lambda b, i, j: (b, 0, i, 0))
    w_spec = pl.BlockSpec((1, J, block), lambda b, i, j: (b, 0, i))
    operands = (qi.transpose(0, 2, 1, 3), ki, w.transpose(0, 2, 1),
                g.astype(jnp.float32))
    dq, dk, dw = pl.pallas_call(
        functools.partial(_index_scores_bwd_kernel, block=block),
        grid=(B, n, n),
        in_specs=[
            q_spec,
            # a tile above the diagonal fetches nothing new
            pl.BlockSpec((1, block, d),
                         lambda b, i, j: (b, jnp.minimum(i, j), 0)),
            w_spec,
            pl.BlockSpec((1, block, block),
                         lambda b, i, j: (b, i, jnp.minimum(i, j))),
        ],
        out_specs=[q_spec,
                   pl.BlockSpec((1, S, d), lambda b, i, j: (b, 0, 0)),
                   w_spec],
        out_shape=[out_struct((B, J, S, d), qi.dtype, *operands),
                   out_struct((B, S, d), jnp.float32, *operands),
                   out_struct((B, J, S), jnp.float32, *operands)],
        scratch_shapes=[pltpu.VMEM((J, block, d), jnp.float32),
                        pltpu.VMEM((J, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret, name="index_scores_bwd",
    )(*operands)
    return (dq.transpose(0, 2, 1, 3), dk.astype(ki.dtype),
            dw.transpose(0, 2, 1).astype(w.dtype))


def index_scores_backward(qi: jax.Array, ki: jax.Array, w: jax.Array,
                          g: jax.Array, block: int = 512,
                          interpret: Optional[bool] = None):
    """:func:`index_scores`' backward alone, ``(dqi, dki, dw)`` from the
    scores' cotangent ``g`` (B, S, S): the one kernel
    (``index_scores_bwd``), for a caller that holds the cotangent in the
    forward pass and must not run the score kernel again to reach it
    (the ``dsa`` layer's indexer: ``layers/seq.py:_index_learned``)."""
    return _index_scores_bwd_call(qi, ki, w, g, block,
                                  use_interpret(interpret))


def _index_scores_fwd(qi, ki, w, block, interpret):
    return index_scores(qi, ki, w, block, interpret), (qi, ki, w)


def _index_scores_bwd(block, interpret, res, g):
    return index_scores_backward(*res, g, block, interpret)


index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


def select_topk_reference(scores: jax.Array, topk: int) -> jax.Array:
    """The EXACT selection of each query's ``topk`` largest ``scores``
    over its causal keys ``s <= t``, (B, S, S) float32 -> boolean (B, S,
    S): every causal key while ``t < topk``, and a tie at the last place
    goes to the lower ``s``, as ``lax.top_k`` breaks it. No sort: the
    ``topk``-th largest value of a row is found bit by bit on the scores'
    order-preserving integer image (32 counting passes over the square),
    and the ties' cut the same way over the positions (one pass a bit of
    the row's length). XLA's loops, each pass a read of the whole square:
    the ``ref`` path and the oracle :func:`select_rows` is tested against.
    Nothing here has a derivative."""
    scores = lax.stop_gradient(scores)
    B, S, Sk = scores.shape
    t = lax.broadcasted_iota(jnp.int32, (1, S, 1), 1)
    s = lax.broadcasted_iota(jnp.int32, (1, 1, Sk), 2)
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    # float order as unsigned order: a negative float's magnitude bits
    # are flipped, and the sign bit is flipped throughout
    u = lax.bitcast_convert_type(
        bits ^ ((bits >> 31) | jnp.int32(-2 ** 31)), jnp.uint32)
    u = jnp.where(s <= t, jnp.maximum(u, jnp.uint32(1)), jnp.uint32(0))
    want = jnp.minimum(t[..., 0] + 1, topk)                  # (1, S)

    def value_bit(i, tau):
        cand = tau | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= want, cand, tau)
    tau = lax.fori_loop(0, 32, value_bit, jnp.zeros((B, S), jnp.uint32))
    above = u > tau[..., None]
    tied = u == tau[..., None]
    need = want - jnp.sum(above, axis=-1, dtype=jnp.int32)   # >= 1
    nbits = max(Sk, 1).bit_length()

    def place_bit(i, cut):
        cand = cut | (jnp.int32(1) << (nbits - 1 - i))
        n = jnp.sum(tied & (s < cand[..., None]), axis=-1, dtype=jnp.int32)
        return jnp.where((cand <= Sk) & (n < need), cand, cut)
    # the largest ``cut`` with fewer than ``need`` ties before it: the
    # ties up to and including position ``cut`` are the ``need`` lowest
    cut = lax.fori_loop(0, nbits, place_bit, jnp.zeros((B, S), jnp.int32))
    return above | (tied & (s <= cut[..., None]))


#: the most rows of the scores a step of ``select_rows`` holds in VMEM,
#: the rows a group of its passes runs over, and the columns a step of a
#: pass reads (PERF.md section 6 has the sweep on the chip that set them)
SELECT_ROWS, _SELECT_GROUP, _SELECT_WIDTH = 128, 64, 512


def _select_rows_kernel(s_ref, o_ref, img_ref, tau_ref, cut_ref, *, topk,
                        group, width):
    """One (batch, row block) step of :func:`select_rows`: the block's
    causal columns are made into the order-preserving integer image once
    (signed: a negative float's magnitude bits flipped; a non-causal key
    the least int32, a causal one above it), a group of rows at a time
    runs :func:`select_topk_reference`'s passes over the image in VMEM —
    each pass counts a row's hits lane-wise over the group's causal
    chunks and sums the lanes once — and the int8 block is written once
    from each row's threshold and cut. A block under ``topk`` keeps every
    causal key and makes no pass; a group whose rows' thresholds need
    every tie makes no position pass."""
    R, S = img_ref.shape
    t0 = pl.program_id(1) * R
    lo = jnp.int32(-2 ** 31)
    cols = lambda c: pl.ds(pl.multiple_of(c * width, width), width)
    lanes = lambda c, n: c * width + lax.broadcasted_iota(
        jnp.int32, (n, width), 1)
    # the chunks that hold a causal key of the block's first ``n`` rows
    reach = lambda n: (t0 + n + width - 1) // width

    def image(c, carry):
        bits = lax.bitcast_convert_type(s_ref[0, :, cols(c)], jnp.int32)
        v = bits ^ ((bits >> 31) & jnp.int32(2 ** 31 - 1))
        t = t0 + lax.broadcasted_iota(jnp.int32, (R, width), 0)
        img_ref[:, cols(c)] = jnp.where(lanes(c, R) <= t,
                                        jnp.maximum(v, lo + 1), lo)
        return carry
    lax.fori_loop(0, reach(R), image, 0)
    # every causal key, none cut: what a row under ``topk`` keeps
    tau_ref[...] = jnp.full(tau_ref.shape, lo + 1)
    cut_ref[...] = jnp.full(cut_ref.shape, S)

    def passes(g, carry):
        rows = pl.ds(pl.multiple_of(g * group, group), group)
        t = t0 + g * group + lax.broadcasted_iota(jnp.int32, (group, 1), 0)
        want = jnp.minimum(t + 1, topk)
        zero = jnp.zeros((group, 1), jnp.int32)

        def count(hit):
            def chunk(c, acc):
                return acc + jnp.where(hit(img_ref[rows, cols(c)], c), 1, 0)
            acc = lax.fori_loop(0, reach((g + 1) * group), chunk,
                                jnp.zeros((group, width), jnp.int32))
            return jnp.sum(acc, axis=1, keepdims=True)

        # a row's threshold is broadcast over a chunk once a pass, not
        # once a chunk
        wide = lambda a: jnp.broadcast_to(a, (group, width))

        def value_bit(i, carry):
            tau, n_tau = carry
            cand = tau | (jnp.int32(1) << (31 - i))
            at = wide(cand ^ lo)
            n = count(lambda x, c: x >= at)
            take = n >= want
            return jnp.where(take, cand, tau), jnp.where(take, n, n_tau)
        tau, n_tau = lax.fori_loop(0, 32, value_bit, (zero, zero))
        ts = tau ^ lo
        tau_ref[rows, :] = ts

        # a row with more keys at its threshold than it keeps cuts them
        # by position: the lowest ``need``
        @pl.when(jnp.any(n_tau != want))
        def _ties():
            at = wide(ts)
            need = want - count(lambda x, c: x > at)
            nbits = S.bit_length()

            def place_bit(i, cut):
                cand = cut | (jnp.int32(1) << (nbits - 1 - i))
                below = wide(cand)
                n = count(lambda x, c: (x == at) & (lanes(c, group) < below))
                return jnp.where((cand <= S) & (n < need), cand, cut)
            cut_ref[rows, :] = lax.fori_loop(0, nbits, place_bit, zero)
        return carry

    @pl.when(t0 + R > topk)
    def _select():
        lax.fori_loop(0, R // group, passes, 0)

    def write(c, carry):
        x, ts = img_ref[:, cols(c)], tau_ref[...]
        keep = (x > ts) | ((x == ts) & (lanes(c, R) <= cut_ref[...]))
        o_ref[0, :, cols(c)] = keep.astype(o_ref.dtype)
        return carry
    lax.fori_loop(0, reach(R), write, 0)

    def clear(c, carry):
        o_ref[0, :, cols(c)] = jnp.zeros((R, width), o_ref.dtype)
        return carry
    lax.fori_loop(reach(R), S // width, clear, 0)


def _select_rows_vmem(rows: int, positions: int) -> int:
    """VMEM bytes a step of :func:`select_rows` holds: the float32 rows
    and the int8 set twice each (the pipeline's buffers), the integer
    image, and each row's threshold and cut a 128-lane row."""
    return rows * positions * (2 * 4 + 2 + 4) + 2 * rows * 128 * 4


def select_rows_block(positions: int) -> int:
    """The rows a step of :func:`select_rows` takes at ``positions``: the
    whole square where there are at most ``SELECT_ROWS``, else the
    largest of ``SELECT_ROWS``, .., 64, 32 that divides them and fits
    the kernel's VMEM, on rows of whole 128-lane chunks; 0 where none
    does (no kernel)."""
    if positions <= SELECT_ROWS:
        return positions
    rows = SELECT_ROWS if positions % 128 == 0 else 0
    while rows >= 32 and (positions % rows or _select_rows_vmem(
            rows, positions) > _BWD_VMEM_LIMIT):
        rows //= 2
    return rows if rows >= 32 else 0


@functools.partial(jax.jit, static_argnums=(1, 2, 3), inline=True)
def _select_rows_call(scores, topk, rows, interpret):
    B, S, _ = scores.shape
    need = _select_rows_vmem(rows, S)
    if need > _BWD_VMEM_LIMIT:
        raise ValueError(
            f"select_rows: {rows} rows of {S} scores, their image and set "
            f"need {need} bytes of VMEM, over the {_BWD_VMEM_LIMIT} the "
            f"kernel may use: take fewer rows")
    # a block under ``topk`` keeps its causal keys whatever they score:
    # it fetches the first block that selects, which that one then reuses
    first = min(topk // rows, S // rows - 1)
    return pl.pallas_call(
        functools.partial(_select_rows_kernel, topk=topk,
                          group=math.gcd(rows, _SELECT_GROUP),
                          width=math.gcd(S, _SELECT_WIDTH)),
        grid=(B, S // rows),
        in_specs=[pl.BlockSpec((1, rows, S),
                               lambda b, i: (b, jnp.maximum(i, first), 0))],
        out_specs=pl.BlockSpec((1, rows, S), lambda b, i: (b, i, 0)),
        out_shape=out_struct((B, S, S), jnp.int8, scores),
        scratch_shapes=[pltpu.VMEM((rows, S), jnp.int32),
                        pltpu.VMEM((rows, 1), jnp.int32),
                        pltpu.VMEM((rows, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret, name="select_rows",
    )(scores)


def select_rows(scores: jax.Array, topk: int, rows: int,
                interpret: Optional[bool] = None) -> jax.Array:
    """:func:`select_topk_reference`'s set, bit for bit, as int8 (1 a
    selected pair) from ONE Pallas kernel (``select_rows``): ``rows``
    rows of the scores at a time (:func:`select_rows_block`) stay in
    VMEM through all the counting passes, so the square is read from
    memory once and the set written once, where the reference reads it
    once a pass. No derivative."""
    scores = lax.stop_gradient(scores).astype(jnp.float32)
    return _select_rows_call(scores, topk, rows, use_interpret(interpret))


def select_topk(scores: jax.Array, topk: int,
                kernel: Optional[bool] = None) -> jax.Array:
    """The exact selection as int8: :func:`select_rows` where ``kernel``
    (``None``: on a TPU backend) and a row block divides the positions,
    else :func:`select_topk_reference`'s."""
    rows = select_rows_block(scores.shape[1])
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if kernel and rows:
        return select_rows(scores, topk, rows)
    return select_topk_reference(scores, topk).astype(jnp.int8)


def select_tiles(select: jax.Array, block_q: int, block_k: int):
    """The kernels' table of a selection (B, Sq, Sk) at blocks of
    ``(block_q, block_k)``, two flat int32 arrays over (batch, q-block,
    k-block): a tile's class — 0 no pair selected (neither computed nor
    fetched), 1 some (masked by the selection's tile), 2 all (multiplied
    whole, no mask) — and the k-block a cell FETCHES: its own where it is
    executed, else the last executed before it (the first of the row
    where none is), so that a skipped tile moves nothing."""
    B, Sq, Sk = select.shape
    nq, nk = Sq // block_q, Sk // block_k
    n = jnp.sum((select != 0).reshape(B, nq, block_q, nk, block_k),
                axis=(2, 4), dtype=jnp.int32)
    cls = jnp.where(n == 0, 0, jnp.where(n == block_q * block_k, 2, 1))
    j = lax.broadcasted_iota(jnp.int32, cls.shape, 2)
    last = lax.cummax(jnp.where(cls > 0, j, -1), axis=2)
    first = jnp.argmax(cls > 0, axis=2).astype(jnp.int32)[..., None]
    fetch = jnp.where(last < 0, first, last)
    return cls.reshape(-1).astype(jnp.int32), fetch.reshape(-1)


def _flash_fwd_select_kernel(cls_ref, fetch_ref, q_ref, k_ref, v_ref, sel_ref,
                             o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                             scale, block_q, block_k, heads):
    """``_flash_fwd_kernel`` under a selection operand: the tile's class
    comes from the table (:func:`select_tiles`, scalar-prefetched), a
    tile without a selected pair is skipped and fetches nothing (the
    index maps read the table's ``fetch``), a tile whose every pair is
    selected is multiplied whole, and any other is masked by the
    selection's own tile. The selection carries causality."""
    b, qi, kj = (pl.program_id(a) for a in range(3))
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    cls = cls_ref[(b // heads * nq + qi) * nk + kj]
    whole_q, whole_k = pl.ds(0, block_q), pl.ds(0, block_k)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    attend = _fwd_attend(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale)

    @pl.when(cls == 2)
    def _all():
        attend(whole_q, ((whole_k, None),))

    @pl.when(cls == 1)
    def _some():
        attend(whole_q, ((whole_k, sel_ref[0].astype(jnp.float32) > 0.0),))

    @pl.when(kj == nk - 1)
    def _finish():
        _fwd_finish(o_ref, lse_ref, acc_ref, m_ref, l_ref)


@functools.partial(jax.jit, static_argnums=tuple(range(6, 12)), inline=True)
def _forward_select_call(cls, fetch, qt, kt, vt, sel, heads, G, scale,
                         block_q, block_k, interpret):
    (BH, Sq, D), Sk, Dv = qt.shape, kt.shape[1], vt.shape[-1]
    nq, nk = Sq // block_q, Sk // block_k
    at = lambda b, i, j, fetch: fetch[(b // heads * nq + i) * nk + j]
    kv_map = lambda b, i, j, cls, fetch: (b // G, at(b, i, j, fetch), 0)
    return pl.pallas_call(
        functools.partial(_flash_fwd_select_kernel, scale=scale,
                          block_q=block_q, block_k=block_k, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(BH, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), kv_map),
                pl.BlockSpec((1, block_k, Dv), kv_map),
                pl.BlockSpec((1, block_q, block_k),
                             lambda b, i, j, cls, fetch: (
                                 b // heads, i, at(b, i, j, fetch))),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, Dv), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i, j, *_: (b, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((block_q, Dv), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32)]),
        out_shape=[out_struct((BH, Sq, Dv), qt.dtype, qt, kt, vt),
                   out_struct((BH, Sq, 1), jnp.float32, qt, kt, vt)],
        interpret=interpret, name="flash_fwd_select",
    )(cls, fetch, qt, kt, vt, sel)


def _flash_bwd_select_kernel(cls_ref, fetch_ref, q_ref, k_ref, v_ref, do_ref,
                             lse_ref, delta_ref, sel_ref, dq_ref, dk_ref,
                             dv_ref, dk_acc, dv_acc, *, scale, block_q,
                             block_k, heads):
    """``_flash_bwd_kernel`` under a selection operand, the table and the
    selection TRANSPOSED as the tile is held: (batch, k-block, q-block)."""
    b, kj, qi = (pl.program_id(a) for a in range(3))
    nk, nq = pl.num_programs(1), pl.num_programs(2)
    cls = cls_ref[(b // heads * nk + kj) * nq + qi]

    @pl.when(jnp.logical_and(kj == 0, qi == 0))
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    grads = _bwd_part_grads(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_acc, dv_acc, scale, qi * block_q)
    whole_q, whole_k = pl.ds(0, block_q), pl.ds(0, block_k)

    @pl.when(cls == 2)
    def _all():
        grads(whole_q, ((whole_k, None),))

    @pl.when(cls == 1)
    def _some():
        grads(whole_q, ((whole_k, sel_ref[0].astype(jnp.float32) > 0.0),))

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=tuple(range(9, 15)), inline=True)
def _backward_select_call(cls, fetch, qt, kt, vt, dot, lse, delta, sel_t,
                          heads, G, scale, block_q, block_k, interpret):
    (BH, Sq, D), Sk, Dv = qt.shape, kt.shape[1], vt.shape[-1]
    nq, nk = Sq // block_q, Sk // block_k
    at = lambda b, j, i, fetch: fetch[(b // heads * nk + j) * nq + i]
    q_map = lambda b, j, i, cls, fetch: (b, at(b, j, i, fetch), 0)
    r_map = lambda b, j, i, cls, fetch: (b, 0, at(b, j, i, fetch))
    kv_map = lambda b, j, i, *_: (b // G, j, 0)
    own = lambda b, j, i, *_: (b, j, 0)
    part = (lambda a: a.dtype) if G == 1 else (lambda a: jnp.float32)
    return pl.pallas_call(
        functools.partial(_flash_bwd_select_kernel, scale=scale,
                          block_q=block_q, block_k=block_k, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(BH, nk, nq),
            in_specs=[pl.BlockSpec((1, block_q, D), q_map),
                      pl.BlockSpec((1, block_k, D), kv_map),
                      pl.BlockSpec((1, block_k, Dv), kv_map),
                      pl.BlockSpec((1, block_q, Dv), q_map),
                      pl.BlockSpec((1, 1, block_q), r_map),
                      pl.BlockSpec((1, 1, block_q), r_map),
                      pl.BlockSpec((1, block_k, block_q),
                                   lambda b, j, i, cls, fetch: (
                                       b // heads, j, at(b, j, i, fetch)))],
            out_specs=[pl.BlockSpec((1, Sq, D), lambda b, j, i, *_: (b, 0, 0)),
                       pl.BlockSpec((1, block_k, D), own),
                       pl.BlockSpec((1, block_k, Dv), own)],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, Dv), jnp.float32)]),
        out_shape=[out_struct((BH, Sq, D), jnp.float32, qt, kt, vt, dot),
                   out_struct((BH, Sk, D), part(kt), qt, kt, vt, dot),
                   out_struct((BH, Sk, Dv), part(vt), qt, kt, vt, dot)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret, name="flash_bwd_select",
    )(cls, fetch, qt, kt, vt, dot, lse, delta, sel_t)


#: the selection as the kernels read it (int8, 1 a selected pair), by the
#: name a ``jax.checkpoint`` policy keeps it under: ``model.py`` under
#: ``remat = 1`` keeps it beside ``FLASH_RESIDUALS``, so that the rebuilt
#: forward neither scores for nor makes the selection a second time and
#: the backward masks by the very set the forward attended
SELECT_RESIDUAL = "dsa_select"

#: a ``dsa`` layer's indexer's gradients for a cotangent of 1, made in the
#: forward pass (``layers/seq.py:_index_learned``): kept, the rebuilt
#: forward makes none of the indexer's loss, its scores or the head sum
INDEX_GRAD_RESIDUAL = "dsa_index_grad"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_select(q: jax.Array, k: jax.Array, v: jax.Array,
                           select: jax.Array, scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: Optional[bool] = None):
    """:func:`flash_attention` over the pairs ``select`` keeps: (B, Sq,
    Sk) int8, 1 a pair every head may attend, which carries causality
    (``select_topk``'s set lies under the diagonal). Grouped key/value
    heads as there. A tile without a selected pair is neither computed
    nor fetched, one wholly selected is multiplied without a mask
    (:func:`select_tiles`), forward and backward; the backward is the one
    kernel at the forward's blocks, reading the selection transposed.
    Returns ``(out, logsumexp (B*H, Sq))``, which carry
    ``FLASH_RESIDUALS``' names; the logsumexp is there for
    :func:`head_sum_probs` and takes no cotangent."""
    out, lse = _flash_select_forward(q, k, v, select, scale, block_q,
                                     block_k, use_interpret(interpret))
    return out, lse.reshape(lse.shape[:2])


def _flash_select_forward(q, k, v, select, scale, block_q, block_k,
                          interpret):
    B, Sq, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    block_q, block_k = _blocks(Sq, Sk, block_q, block_k, True, None)
    cls, fetch = select_tiles(select, block_q, block_k)
    out, lse = _forward_select_call(
        cls, fetch, _heads_flat(q), _heads_flat(k), _heads_flat(v), select,
        H, _group(q, k), _scale(q, scale), block_q, block_k, interpret)
    return out.reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3), lse


def _flash_select_fwd_rule(q, k, v, select, scale, block_q, block_k,
                           interpret):
    out, lse = _flash_select_forward(q, k, v, select, scale, block_q,
                                     block_k, use_interpret(interpret))
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse.reshape(lse.shape[:2]), FLASH_RESIDUALS[1])
    return (out, lse), (q, k, v, select, out, lse)


def _flash_select_bwd_rule(scale, block_q, block_k, interpret, res, g):
    q, k, v, select, out, lse = res
    block_q, block_k = _bwd_blocks(q, k, block_q, block_k, True, None)
    sel_t = select.transpose(0, 2, 1)
    dq, dk, dv = _backward_select_call(
        *select_tiles(sel_t, block_k, block_q),
        # the logsumexp's cotangent is dropped: it feeds a detached target
        *_bwd_operands(q, k, v, out, lse, g[0]), sel_t, q.shape[2],
        _group(q, k), _scale(q, scale), block_q, block_k,
        use_interpret(interpret))
    return (*_bwd_results(dq, dk, dv, q, k, v), None)


flash_attention_select.defvjp(_flash_select_fwd_rule, _flash_select_bwd_rule)


def head_sum_probs_reference(q, k, select, scale=None) -> jax.Array:
    """``sum_h softmax_h over the selected set / H``, (B, Sq, Sk) float32:
    the main attention's distribution summed over its heads, on XLA's
    dots (positions x positions x heads in memory: the tests' size)."""
    B, Sq, H, D = q.shape
    G = _group(q, k)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(B, Sq, H // G, G, D), k,
                   preferred_element_type=jnp.float32) * _scale(q, scale)
    keep = (select != 0)[:, None, None]
    p = jax.nn.softmax(jnp.where(keep, s, _NEG), axis=-1)
    return jnp.sum(jnp.where(keep, p, 0.0), axis=(1, 2)) / H


def _head_sum_kernel(cls_ref, q_ref, k_ref, lse_ref, sel_ref, o_ref, *,
                     scale, heads):
    """One (batch, q-block, k-block, head) cell, the heads innermost: the
    head's probabilities of the tile, rebuilt from its logsumexp, are
    added into the float32 tile, which stays in VMEM across the heads;
    the last head's cell masks it by the selection and divides."""
    b, qi, kj, h = (pl.program_id(a) for a in range(4))
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    cls = cls_ref[(b * nq + qi) * nk + kj]

    @pl.when(h == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(cls > 0)
    def _add():
        sc = lax.dot_general(q_ref[0], k_ref[0], _NT,
                             preferred_element_type=jnp.float32) * scale
        o_ref[0] += jnp.exp(sc - lse_ref[0])

    @pl.when(jnp.logical_and(cls > 0, h == heads - 1))
    def _finish():
        o_ref[0] = jnp.where(sel_ref[0].astype(jnp.float32) > 0.0,
                             o_ref[0] * (1.0 / heads), 0.0)


@functools.partial(jax.jit, static_argnums=tuple(range(5, 10)), inline=True)
def _head_sum_call(cls, qt, kt, lse, sel, heads, G, scale, block, interpret):
    (BH, Sq, D), Sk = qt.shape, kt.shape[1]
    B, nq, nk = BH // heads, Sq // block, Sk // block
    return pl.pallas_call(
        functools.partial(_head_sum_kernel, scale=scale, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nq, nk, heads),
            in_specs=[
                pl.BlockSpec((1, block, D),
                             lambda b, i, j, h, *_: (b * heads + h, i, 0)),
                pl.BlockSpec((1, block, D), lambda b, i, j, h, *_: (
                    (b * heads + h) // G, j, 0)),
                pl.BlockSpec((1, block, 1),
                             lambda b, i, j, h, *_: (b * heads + h, i, 0)),
                pl.BlockSpec((1, block, block),
                             lambda b, i, j, h, *_: (b, i, j)),
            ],
            out_specs=pl.BlockSpec((1, block, block),
                                   lambda b, i, j, h, *_: (b, i, j))),
        out_shape=out_struct((B, Sq, Sk), jnp.float32, qt, kt, lse),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret, name="head_sum_probs",
    )(cls, qt, kt, lse, sel)


def head_sum_probs(q: jax.Array, k: jax.Array, lse: jax.Array,
                   select: jax.Array, scale: Optional[float] = None,
                   block: int = 128,
                   interpret: Optional[bool] = None) -> jax.Array:
    """The main attention's distribution summed over its heads, ``sum_h
    a_h[t, s] / H`` over the selected set, (B, Sq, Sk) float32, as a
    Pallas kernel (``head_sum_probs``): a head's probabilities are rebuilt
    a tile at a time from ``lse`` — the logsumexp (B*H, Sq) the selection
    kernel's forward emitted — and summed in VMEM, so nothing of positions
    x positions x heads is ever in memory. ``select`` as
    :func:`flash_attention_select` takes it. No derivative: the indexer's
    target is detached. The ``dsa`` layer calls it once a step, in the
    forward pass, where the indexer's whole backward runs too and only
    its gradients are kept (``INDEX_GRAD_RESIDUAL``): the forward that
    ``remat`` rebuilds has no use for the distribution."""
    q, k, lse = (lax.stop_gradient(a) for a in (q, k, lse))
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    block, _ = _blocks(Sq, Sk, block, block, True, None)
    cls, _ = select_tiles(select, block, block)
    out = _head_sum_call(
        cls, _heads_flat(q), _heads_flat(k), lse.reshape(B * H, Sq, 1),
        select, H, _group(q, k), _scale(q, scale), block,
        use_interpret(interpret))
    return lax.stop_gradient(out)
