"""The one home of toolchain-specific spellings.

Written for the installed jax (0.9): Pallas TPU compiler params are
``pltpu.CompilerParams`` and ``shard_map`` is ``jax.shard_map`` with
``check_vma``. Every call site uses the names below, so the next rename
is a one-file fix.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.experimental.pallas import tpu as pltpu


def tpu_compiler_params(**kwargs: Any):
    """Pallas TPU compiler params. An unknown keyword is an error (the
    class raises), never silently dropped."""
    return pltpu.CompilerParams(**kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=frozenset(),
              check_vma=True):
    """``jax.shard_map`` under the repository's one spelling."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=axis_names,
                         check_vma=check_vma)
