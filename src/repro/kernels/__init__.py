"""Pallas TPU kernels. Each ``kernel.py`` builds the ``pallas_call``
(compiled by default); each ``ops.py`` is the jitted wrapper that
callers use, and :func:`interpret_default` is the one place that picks
interpret mode: on any backend other than a TPU."""
import jax


def interpret_default(interpret):
    """``interpret`` as given, or — when None — interpret mode exactly
    when the default backend is not a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
