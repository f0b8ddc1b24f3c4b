"""Pallas TPU kernel: position-masked GQA flash attention with fused
Cache-Craft chunk-mass statistics.

TPU adaptation of the paper's Triton partial-prefill kernel (§4
"Selective Token Recomputation"): query rows are the *active* tokens
(new chunks + recompute + question) gathered into a dense [A, H, D]
block; keys/values are the merged (cached + fresh) KV. Causality is a
position predicate, not a triangular mask. Instead of materializing
QK^T to derive inter/intra attention (the paper's GPU approach), the
per-(row, key-chunk) softmax mass is accumulated *inside* the flash
loop with one extra [bq,bk]x[bk,C] MXU product per tile, so the O(S^2)
attention matrix never leaves VMEM.

Grid: (q_blocks, H, kv_blocks), kv innermost sequential; the running
max / denominator / output / mass accumulators live in VMEM scratch
that persists across the kv dimension; the mass output block (indexed
by q only) is accumulated across heads via consecutive revisiting.
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


def _kernel(qp_ref, qs_ref, kp_ref, ks_ref, kc_ref, q_ref, k_ref, v_ref,
            o_ref, mass_ref, m_s, l_s, acc, massacc, *,
            scale: float, window: int, num_chunks: int):
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    h = pl.program_id(1)

    @pl.when(j == 0)
    def _init_head():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)
        massacc[...] = jnp.zeros_like(massacc)

    @pl.when((j == 0) & (h == 0))
    def _init_mass():
        mass_ref[...] = jnp.zeros_like(mass_ref)

    q = q_ref[...].astype(jnp.float32)                  # [bq, D]
    k = k_ref[...].astype(jnp.float32)                  # [bk, D]
    v = v_ref[...].astype(jnp.float32)
    qpos = qp_ref[...]                                  # [bq, 1]
    kpos = kp_ref[...]                                  # [1, bk]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = (qpos >= kpos) & (qpos >= 0) & (kpos >= 0)
    if window:
        mask &= (qpos - kpos) < window
    # per-request segment mask: packed multi-request prefill confines a
    # query row to keys of its own request
    mask &= qs_ref[...] == ks_ref[...]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_s[...]                                   # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_new = jnp.maximum(m_new, NEG_INF / 2)
    p = jnp.exp(s - m_new)                              # [bq, bk]
    corr = jnp.exp(m_prev - m_new)                      # [bq, 1]
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    # chunk one-hot of the key block, chunk-major [C, bk]
    iota = jax.lax.broadcasted_iota(jnp.int32, (num_chunks, p.shape[1]), 0)
    onehot = (kc_ref[...] == iota).astype(jnp.float32)
    massacc[...] = massacc[...] * corr + jax.lax.dot_general(
        p, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[...] = (acc[...] / l).astype(o_ref.dtype)
        mass_ref[...] += massacc[...] / l


def chunk_attention_pallas(q, k, v, q_pos, k_pos, k_chunk, *,
                           q_seg=None, k_seg=None,
                           num_chunks: int = 16, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q [A,H,D], k/v [S,Hkv,D], q_pos [A], k_pos [S], k_chunk [S].
    ``q_seg`` [A] / ``k_seg`` [S] (optional) carry packed-request segment
    ids; attention never crosses segments. Shapes must be pre-padded:
    A % block_q == 0 and S % block_k == 0 (padding rows use position
    -1). Returns (out [A,H,D], mass [A,C]).

    TPU layout: heads are folded into the lane axis (q [A, H*D], k/v
    [S, Hkv*D]), so one head's tile is a (rows, D) block and no block
    ever slices the head axis in the second-minor position. Query-side
    vectors are columns [A, 1]; key-side vectors are rows [1, S], so the
    [bq, bk] mask is a plain broadcast."""
    A, H, D = q.shape
    S, Hkv = k.shape[0], k.shape[1]
    G = H // Hkv
    nq, nk = A // block_q, S // block_k
    col = lambda x: x.reshape(A, 1).astype(jnp.int32)      # noqa: E731
    row = lambda x: x.reshape(1, S).astype(jnp.int32)      # noqa: E731
    qs = col(jnp.zeros((A,), jnp.int32) if q_seg is None else q_seg)
    ks = row(jnp.zeros((S,), jnp.int32) if k_seg is None else k_seg)

    grid = (nq, H, nk)
    kernel = functools.partial(_kernel, scale=1.0 / np.sqrt(D),
                               window=window, num_chunks=num_chunks)
    q_col = pl.BlockSpec((block_q, 1), lambda i, h, j: (i, 0))
    k_row = pl.BlockSpec((1, block_k), lambda i, h, j: (0, j))
    out, mass = pl.pallas_call(
        kernel,
        name="chunk_attention",
        grid=grid,
        in_specs=[
            q_col, q_col, k_row, k_row, k_row,
            pl.BlockSpec((block_q, D), lambda i, h, j: (i, h)),
            pl.BlockSpec((block_k, D), lambda i, h, j: (j, h // G)),
            pl.BlockSpec((block_k, D), lambda i, h, j: (j, h // G)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, D), lambda i, h, j: (i, h)),
            pl.BlockSpec((block_q, num_chunks), lambda i, h, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((A, H * D), q.dtype),
            jax.ShapeDtypeStruct((A, num_chunks), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, num_chunks), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(col(q_pos), qs, row(k_pos), ks, row(k_chunk), q.reshape(A, H * D),
      k.reshape(S, Hkv * D), v.reshape(S, Hkv * D))
    return out.reshape(A, H, D), mass
