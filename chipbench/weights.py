"""Random weights from ``--seed``, made on the device in one jitted call,
in the type they are served in, laid out as the program's parameter tree
(``groups[0]`` holds every layer stacked on the leading axis).

Scales follow the usual fan-in rule, and the norm gains are stored as
offsets from one (``x * (1 + w)``), zero here."""
from __future__ import annotations

import numpy as np


def _key(seed: int):
    import jax
    k = jax.random.key(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, int(seed) >> 32)


def weight_shapes(m) -> dict:
    """Leaf -> (shape, scale); scale 0 means zeros."""
    L, d, h, hkv, dh, f, V = (m.layers, m.d_model, m.heads, m.kv_heads,
                              m.head_dim, m.d_ff, m.vocab)
    return {
        "embed": ((V, d), 0.02),
        "unembed": ((d, V), 1 / np.sqrt(d)),
        "final_norm": ((d,), 0.0),
        "ln1": ((L, d), 0.0),
        "wq": ((L, d, h, dh), 1 / np.sqrt(d)),
        "wk": ((L, d, hkv, dh), 1 / np.sqrt(d)),
        "wv": ((L, d, hkv, dh), 1 / np.sqrt(d)),
        "wo": ((L, h, dh, d), 1 / np.sqrt(h * dh)),
        "ln2": ((L, d), 0.0),
        "wi": ((L, d, 2, f), 1 / np.sqrt(d)),
        "wo_ff": ((L, f, d), 1 / np.sqrt(f)),
    }


_LAYER = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wi", "wo_ff")


def make_weights(m, seed: int, dtype="float32"):
    import jax
    import jax.numpy as jnp
    shapes = weight_shapes(m)
    dt = jnp.dtype(dtype)

    @jax.jit
    def gen(key):
        out = {}
        for i, (name, (shape, scale)) in enumerate(shapes.items()):
            if scale == 0.0:
                out[name] = jnp.zeros(shape, dt)
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * scale
                out[name] = x.astype(dt)
        return {"embed": out["embed"], "unembed": out["unembed"],
                "final_norm": out["final_norm"],
                "groups": [{n: out[n] for n in _LAYER}], "tail": []}

    return gen(_key(seed))
