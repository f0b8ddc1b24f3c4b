"""Jitted wrapper for the RoPE kernel (batched)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_default
from repro.kernels.rope.kernel import rope_pallas


@functools.partial(jax.jit, static_argnames=("theta", "inverse", "block_t",
                                              "interpret"))
def rope(x, pos, *, theta: float, inverse: bool = False,
         block_t: int = 256, interpret: bool | None = None):
    """x [T,H,D] or [B,T,H,D]; pos [T] or [B,T]."""
    fn = functools.partial(rope_pallas, theta=theta, inverse=inverse,
                           block_t=block_t,
                           interpret=interpret_default(interpret))
    if x.ndim == 4:
        return jax.vmap(fn)(x, pos)
    return fn(x, pos)
