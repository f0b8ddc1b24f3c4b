"""Jitted wrapper for the SSD intra-chunk kernel (batched)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_default
from repro.kernels.ssd.kernel import ssd_intra_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra(xdt, log_a, B_mat, C_mat, *, interpret: bool | None = None):
    """xdt [B,nC,L,H,P] or [nC,L,H,P]; see kernel.ssd_intra_pallas."""
    fn = functools.partial(ssd_intra_pallas,
                           interpret=interpret_default(interpret))
    if xdt.ndim == 5:
        return jax.vmap(fn)(xdt, log_a, B_mat, C_mat)
    return fn(xdt, log_a, B_mat, C_mat)
