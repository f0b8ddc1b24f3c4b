"""Pallas TPU kernel: apply / remove rotary position embeddings.

TPU analogue of the paper's custom CUDA kernel (§4 "RPE Management"):
chunk-caches are stored with K *un-rotated* so they can be re-injected at
arbitrary positions; this kernel applies the rotation x*cos - y*sin /
x*sin + y*cos (and its inverse, sign=-1) over [T, H, D] blocks with the
angle recomputed in-register from the position vector — no cos/sin tables
in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params


def _kernel(pos_ref, x_ref, o_ref, *, theta: float, sign: float,
            head_dim: int):
    x = x_ref[...].astype(jnp.float32)            # [bt, H*D]
    half = head_dim // 2
    width = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    d = lane % head_dim                           # index within the head
    first = d < half
    freq = (d % half).astype(jnp.float32)
    inv_freq = jnp.exp(freq * (-2.0 * np.log(theta) / head_dim))
    ang = pos_ref[...].astype(jnp.float32) * inv_freq   # [bt, H*D]
    cos = jnp.cos(ang)
    sin = jnp.sin(ang) * sign
    # rotate-half partner inside each head: lane d < D/2 pairs with
    # d + D/2, lane d >= D/2 with d - D/2 (rolls never cross a head)
    nxt = pltpu.roll(x, width - half, 1)          # x[c + D/2]
    prv = pltpu.roll(x, half, 1)                  # x[c - D/2]
    out = x * cos + jnp.where(first, -nxt, prv) * sin
    o_ref[...] = out.astype(o_ref.dtype)


def rope_pallas(x, pos, *, theta: float, inverse: bool = False,
                block_t: int = 256, interpret: bool = False):
    """x [T,H,D], pos [T] -> rotated x. inverse=True removes the rotation.
    Heads are folded into lanes ([T, H*D]); ``pos`` is a [T, 1] column."""
    T, H, D = x.shape
    bt = min(block_t, -(-T // 8) * 8)
    pad = (-T) % bt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        pos = jnp.pad(pos, (0, pad))
    Tp = x.shape[0]
    out = pl.pallas_call(
        functools.partial(_kernel, theta=theta,
                          sign=-1.0 if inverse else 1.0, head_dim=D),
        grid=(Tp // bt,),
        in_specs=[
            pl.BlockSpec((bt, 1), lambda i: (i, 0)),
            pl.BlockSpec((bt, H * D), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, H * D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, H * D), x.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pos.reshape(Tp, 1).astype(jnp.int32), x.reshape(Tp, H * D))
    return out.reshape(Tp, H, D)[:T]
