"""Pallas TPU kernel: flash-decode (single new token vs a long KV cache).

One grid cell per kv block, which holds every kv head exactly as the
cache stores it ([rows, Hkv, D]); an in-kernel loop visits the heads,
and the G=H/Hkv grouped query heads of each kv head are processed
together as a [G, D] tile so the MXU contraction stays dense even for
small G. The running max/denominator persists in VMEM scratch across
kv blocks. Masking is positional
(slot position <= query position, optional sliding window), matching the
serving engine's ring buffers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params

NEG_INF = -1e30


def _online_softmax_heads(q_ref, k_ref, v_ref, mask, m_s, l_s, acc, *,
                          scale: float, num_kv_heads: int):
    """One kv block's online-softmax update for every kv head. ``k_ref``
    / ``v_ref`` hold the block with all heads ([bk, Hkv, D], the layout
    the cache stores), so each head's [bk, D] tile is a strided load —
    no relayout of the cache is ever needed. ``q_ref`` is [Hkv, G, D]
    (the G query heads of each kv head), ``mask`` [1, bk]."""
    for h in range(num_kv_heads):
        q = q_ref[h].astype(jnp.float32)                # [G, D]
        k = k_ref[:, h, :].astype(jnp.float32)          # [bk, D]
        v = v_ref[:, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)                 # [G, bk]
        m_prev = m_s[h]                                 # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_new = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[h] = l_s[h] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc[h] = acc[h] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_s[h] = m_new


def _init_scratch(m_s, l_s, acc):
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc[...] = jnp.zeros_like(acc)


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_s, l_s, acc, *, scale: float, window: int,
            num_kv_heads: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_s, l_s, acc)

    qpos = qpos_ref[...]                            # [1, 1]
    kpos = kpos_ref[...]                            # [1, bk]
    mask = (kpos <= qpos) & (kpos >= 0)
    if window:
        mask &= (qpos - kpos) < window
    _online_softmax_heads(q_ref, k_ref, v_ref, mask, m_s, l_s, acc,
                          scale=scale, num_kv_heads=num_kv_heads)

    @pl.when(j == pl.num_programs(0) - 1)
    def _finish():
        o_ref[...] = (acc[...] /
                      jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


def _paged_kernel(rows_ref, qpos_ref, q_ref, k_ref, v_ref, kpos_ref,
                  o_ref, m_s, l_s, acc, *, scale: float, window: int,
                  num_kv_heads: int):
    """One grid cell per (request, kv-block). The kv block is selected
    by the scalar-prefetched block-index row (``rows_ref``): the
    BlockSpec index maps read ``rows_ref[b, j]`` so K/V stream straight
    out of the pool's block arena — no gathered copy exists. Padding
    blocks (row entry -1) are clamped to block 0 by the index map and
    masked away here; padding *slots* inside a live block carry pool
    position -1 and mask the same way, so block-aligned layouts with
    interior padding (shared runs) need no compaction."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_s, l_s, acc)

    kpos = kpos_ref[0]                              # [1, bs]
    qpos = qpos_ref[b]                              # scalar
    live = rows_ref[b, j] >= 0                      # padding block?
    mask = live & (kpos <= qpos) & (kpos >= 0)
    if window:
        mask &= (qpos - kpos) < window
    _online_softmax_heads(q_ref.at[0], k_ref.at[0], v_ref.at[0], mask,
                          m_s, l_s, acc, scale=scale,
                          num_kv_heads=num_kv_heads)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc[...] /
                      jnp.maximum(l_s[...], 1e-30))[None].astype(o_ref.dtype)


def _scratch(Hkv, G, D):
    return [pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32)]


def paged_decode_attention_pallas(q, k_blocks, v_blocks, kpos_blocks,
                                  block_rows, q_pos, *, window: int = 0,
                                  interpret: bool = False):
    """Block-table-native decode attention, in place over the pool.

    q [B,H,D]; k_blocks/v_blocks [NB, bs, Hkv, D] — the KV pool's block
    arena exactly as the pool stores it; kpos_blocks [NB, bs] per-slot
    absolute positions (-1 = padding); block_rows [B, NBmax] each
    request's block-id row (-1 padded); q_pos [B] query positions (-1 =
    masked batch row -> zero output). The grid runs (B, NBmax) and the
    block-index row is scalar-prefetched so the K/V BlockSpec index
    maps dereference it — attention reads the pool block storage
    directly (one contiguous [bs, Hkv, D] block per step, every head),
    no per-request gather or arena copy is ever formed. Slot positions
    ride as [NB, 1, bs] rows."""
    B, H, D = q.shape
    NB, bs, Hkv = k_blocks.shape[:3]
    G = H // Hkv
    NBmax = block_rows.shape[1]
    qg = q.reshape(B, Hkv, G, D)
    rows = jnp.asarray(block_rows, jnp.int32)

    def _blk(r, b, j):
        # r is the prefetched rows ref: padding entries read block 0,
        # masked in-kernel via the same ref
        return jnp.maximum(r[b, j], 0)

    kv_spec = pl.BlockSpec((1, bs, Hkv, D), lambda b, j, r, qp:
                           (_blk(r, b, j), 0, 0, 0))
    q_spec = pl.BlockSpec((1, Hkv, G, D), lambda b, j, r, qp: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=1.0 / np.sqrt(D),
                          window=window, num_kv_heads=Hkv),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, NBmax),
            in_specs=[
                q_spec, kv_spec, kv_spec,
                pl.BlockSpec((1, 1, bs), lambda b, j, r, qp:
                             (_blk(r, b, j), 0, 0)),
            ],
            out_specs=q_spec,
            scratch_shapes=_scratch(Hkv, G, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(rows, jnp.asarray(q_pos, jnp.int32), qg, k_blocks, v_blocks,
      jnp.asarray(kpos_blocks, jnp.int32).reshape(NB, 1, bs))
    return out.reshape(B, H, D)


def decode_attention_pallas(q, k, v, q_pos, k_pos, *, window: int = 0,
                            block_k: int = 256, interpret: bool = False):
    """q [H,D], k/v [S,Hkv,D], q_pos scalar [], k_pos [S] -> o [H,D].
    The kv tile is [bk, Hkv, D] (every head, as the cache stores it);
    ``k_pos`` rides as a [1, S] row."""
    H, D = q.shape
    S, Hkv = k.shape[0], k.shape[1]
    G = H // Hkv
    bk = min(block_k, -(-S // 8) * 8)
    pad = (-S) % bk
    if pad:
        k = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-1)
    Sp = k.shape[0]
    qg = q.reshape(Hkv, G, D)
    kv_spec = pl.BlockSpec((bk, Hkv, D), lambda j: (j, 0, 0))
    q_spec = pl.BlockSpec((Hkv, G, D), lambda j: (0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / np.sqrt(D), window=window,
                          num_kv_heads=Hkv),
        grid=(Sp // bk,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, bk), lambda j: (0, j)),
            q_spec, kv_spec, kv_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, G, D), q.dtype),
        scratch_shapes=_scratch(Hkv, G, D),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(q_pos.reshape(1, 1).astype(jnp.int32),
      k_pos.reshape(1, Sp).astype(jnp.int32), qg, k, v)
    return out.reshape(H, D)
