"""Jitted public wrapper for the chunk-attention kernel: padding to block
multiples and optional batch vmap."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.chunk_attention.kernel import chunk_attention_pallas


def _round_up(n, m):
    return max(m, -(-n // m) * m)


def _pad_axis(x, mult, axis, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=(
    "num_chunks", "window", "block_q", "block_k", "interpret"))
def chunk_attention(q, k, v, q_pos, k_pos, k_chunk, *,
                    q_seg=None, k_seg=None,
                    num_chunks: int = 16, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """Batched entry point. q [B,A,H,D] (or [A,H,D]), k/v [B,S,Hkv,D],
    q_pos [B,A], k_pos [B,S], k_chunk [B,S]. Optional ``q_seg``/``k_seg``
    ([B,A]/[B,S]) carry packed-request segment ids so several requests can
    share one sequence row without attending across each other.
    Returns (out, mass).

    Tiles: a block never exceeds the rows it covers rounded up to a
    multiple of 8, and rows are padded up to a whole number of blocks
    (padding carries position -1), so every tile is sublane-aligned."""
    interpret = interpret_default(interpret)
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[None], k[None], v[None]
        q_pos, k_pos, k_chunk = q_pos[None], k_pos[None], k_chunk[None]
        if q_seg is not None:
            q_seg = q_seg[None]
        if k_seg is not None:
            k_seg = k_seg[None]
    B, A0 = q.shape[:2]
    if q_seg is None:
        q_seg = jnp.zeros((B, A0), jnp.int32)
    if k_seg is None:
        k_seg = jnp.zeros((B, k.shape[1]), jnp.int32)
    bq = min(block_q, _round_up(A0, 8))
    bk = min(block_k, _round_up(k.shape[1], 8))
    q = _pad_axis(q, bq, 1)
    q_pos = _pad_axis(q_pos, bq, 1, -1)
    q_seg = _pad_axis(q_seg, bq, 1, -1)
    k = _pad_axis(k, bk, 1)
    v = _pad_axis(v, bk, 1)
    k_pos = _pad_axis(k_pos, bk, 1, -1)
    k_seg = _pad_axis(k_seg, bk, 1, -2)   # != q pad so pads never match
    k_chunk = _pad_axis(k_chunk, bk, 1, num_chunks - 1)

    def fn(q, k, v, qp, kp, kc, qs, ks):
        return chunk_attention_pallas(q, k, v, qp, kp, kc,
                                      q_seg=qs, k_seg=ks,
                                      num_chunks=num_chunks,
                                      window=window, block_q=bq,
                                      block_k=bk, interpret=interpret)

    out, mass = jax.vmap(fn)(q, k, v, q_pos, k_pos, k_chunk, q_seg, k_seg)
    out, mass = out[:, :A0], mass[:, :A0]
    if squeeze:
        out, mass = out[0], mass[0]
    return out, mass
