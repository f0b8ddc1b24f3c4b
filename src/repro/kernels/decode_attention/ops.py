"""Jitted wrapper for flash-decode (batched over requests)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_default
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                              "interpret"))
def decode_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                     block_k: int = 256, interpret: bool | None = None):
    """q [B,H,D], k/v [B,S,Hkv,D], q_pos [B], k_pos [B,S] -> [B,H,D]."""
    fn = functools.partial(decode_attention_pallas, window=window,
                           block_k=block_k,
                           interpret=interpret_default(interpret))
    if q.ndim == 3:
        return jax.vmap(fn)(q, k, v, q_pos, k_pos)
    return fn(q, k, v, q_pos, k_pos)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_blocks, v_blocks, kpos_blocks, block_rows,
                           q_pos, *, window: int = 0,
                           interpret: bool | None = None):
    """Block-table-native decode: q [B,H,D], k_blocks/v_blocks
    [NB, bs, Hkv, D] (the pool arena, in place), kpos_blocks [NB, bs],
    block_rows [B, NBmax] (-1 padded), q_pos [B] -> [B,H,D]. The kv
    tile is the pool block itself — no per-request gather is formed."""
    return paged_decode_attention_pallas(
        q, k_blocks, v_blocks, kpos_blocks, block_rows, q_pos,
        window=window, interpret=interpret_default(interpret))
