"""Pallas TPU kernel: Mamba2 SSD intra-chunk block.

The state-space-duality algorithm splits the sequence into chunks; the
intra-chunk term is attention-like (two [L,L] matmuls) and the chunk-end
state is one more matmul — all MXU work, computed here per (chunk, head)
grid cell. The inter-chunk recurrence (a short sequential scan over
chunk states) stays in JAX. Cumulative decay sums are computed as a
lower-triangular matmul instead of a scan so everything lowers to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params


def _kernel(x_ref, la_ref, b_ref, c_ref, y_ref, st_ref):
    x = x_ref[...][0, :, 0, :].astype(jnp.float32)    # [L, P] (dt-scaled)
    la = la_ref[...][0].astype(jnp.float32)           # [L, 1]
    Bm = b_ref[...][0].astype(jnp.float32)            # [L, N]
    Cm = c_ref[...][0].astype(jnp.float32)            # [L, N]
    L = x.shape[0]

    # cumulative decay via triangular matmul (scan-free, MXU-friendly)
    tri = jnp.tril(jnp.ones((L, L), jnp.float32))     # includes diagonal
    cum = jax.lax.dot(tri, la, preferred_element_type=jnp.float32)  # [L,1]
    seg = cum - cum.T                                  # [L, L] (i,j)=cum_i-cum_j
    mask = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.where(mask, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot(decay * scores, x,
                    preferred_element_type=jnp.float32)            # [L, P]
    y_ref[...] = y[None, :, None, :].astype(y_ref.dtype)

    total = cum[-1:, :]                                # [1,1]
    decay_out = jnp.exp(total - cum)                   # [L,1]
    st = jax.lax.dot_general(Bm * decay_out, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [N, P]
    st_ref[...] = st.T[None, None].astype(st_ref.dtype)  # [1,1,P,N]


def ssd_intra_pallas(xdt, log_a, B_mat, C_mat, *, interpret: bool = False):
    """Intra-chunk SSD. xdt [nC,L,H,P] (x pre-multiplied by dt),
    log_a [nC,L,H], B_mat/C_mat [nC,L,N].

    Returns (y_intra [nC,L,H,P] fp32, chunk_state [nC,H,P,N] fp32)."""
    nC, L, H, P = xdt.shape
    N = B_mat.shape[-1]
    y, st = pl.pallas_call(
        _kernel,
        grid=(nC, H),
        in_specs=[
            pl.BlockSpec((1, L, 1, P), lambda c, h: (c, 0, h, 0)),
            pl.BlockSpec((1, L, 1), lambda c, h: (c, 0, h)),
            pl.BlockSpec((1, L, N), lambda c, h: (c, 0, 0)),
            pl.BlockSpec((1, L, N), lambda c, h: (c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, 1, P), lambda c, h: (c, 0, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda c, h: (c, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nC, L, H, P), jnp.float32),
            jax.ShapeDtypeStruct((nC, H, P, N), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xdt, log_a, B_mat, C_mat)
    return y, st
