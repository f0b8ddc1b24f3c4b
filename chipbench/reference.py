"""The plain reference: a Llama-architecture decoder (DeepSeek LLM's)
in straightforward ``jax.numpy``, float32 at ``highest`` matmul
precision. It imports nothing of the program and reads only the
benchmark's own weights.

Per layer: pre-norm RMSNorm with gain ``1 + w``, rotate-half RoPE,
causal grouped-query attention (query head ``h`` reads key/value head
``h // (H / Hkv)``), SwiGLU feed-forward (``silu(x W_gate) * (x W_up)``
then ``W_down``), residual adds; then a final RMSNorm and the output
head. It runs layer by layer and attention in blocks of query rows, so
that it fits beside nothing else on the chip.

``control=True`` is the control: the same computation with every
matmul operand (weights, activations, attention scores and weights)
rounded to float8 e4m3 under a per-tensor scale and accumulated in
float32: fp8, the step below the bfloat16 weights the configurations
state.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import numpy as np

NEG = -1e30
Q_BLOCK = 512


def _bucket(n: int, b: int) -> int:
    return max(b, -(-n // b) * b)


@functools.lru_cache(maxsize=None)
def _fns(layers, d, h, hkv, dh, f, theta, eps, control):
    import jax
    import jax.numpy as jnp
    dt = jnp.float32
    G = h // hkv

    def q8(x):
        """Round to float8 e4m3 under a per-tensor scale (control only)."""
        if not control:
            return x.astype(dt)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(dt) * scale

    def ein(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    def norm(x, w):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (y * (1.0 + w.astype(jnp.float32))).astype(dt)

    def rope(x, pos):                    # x [T, n, dh]
        half = dh // 2
        freq = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))
        ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               -1).astype(dt)

    @jax.jit
    def layer(hid, pos, valid, gw, l):
        w = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
             for k, v in gw.items()}
        T = hid.shape[0]
        x = norm(hid, w["ln1"])
        q = rope(ein("td,dhk->thk", x, w["wq"]), pos)
        k = rope(ein("td,dhk->thk", x, w["wk"]), pos)
        v = ein("td,dhk->thk", x, w["wv"])
        k = jnp.repeat(k, G, axis=1)     # head h reads kv head h // G
        v = jnp.repeat(v, G, axis=1)
        scale = 1.0 / np.sqrt(dh)

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
            pb = jax.lax.dynamic_slice_in_dim(pos, i * Q_BLOCK, Q_BLOCK)
            s = ein("qhk,shk->hqs", qb, k) * scale
            m = (pb[:, None] >= pos[None, :]) & valid[None, :]
            s = jnp.where(m[None], s, NEG)
            p = jax.nn.softmax(s, axis=-1)
            return ein("hqs,shk->qhk", p, v)

        att = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
        att = att.reshape(T, h, dh)
        hid = hid + ein("thk,hkd->td", att, w["wo"])
        x = norm(hid, w["ln2"])
        gu = ein("td,dgf->tgf", x, w["wi"])
        y = jax.nn.silu(gu[:, 0]) * gu[:, 1]
        return hid + ein("tf,fd->td", y, w["wo_ff"])

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(dt)

    @jax.jit
    def head(hid, rows, fn, unembed):
        return ein("td,dv->tv", norm(hid[rows], fn), unembed)

    return embed, layer, head


def logits_at(weights, m, theta: float, eps: float, tokens: np.ndarray,
              rows: Sequence[int], control: bool = False) -> np.ndarray:
    """Logits [len(rows), V] of the plain forward over ``tokens`` at the
    positions ``rows`` (each predicts the token after it), at ``highest``
    precision (the control: fp8 operands)."""
    import jax
    import jax.numpy as jnp
    embed, layer, head = _fns(m.layers, m.d_model, m.heads, m.kv_heads,
                              m.head_dim, m.d_ff, float(theta), float(eps),
                              bool(control))
    T = len(tokens)
    Tp = _bucket(T, Q_BLOCK)
    tok = np.zeros(Tp, np.int32)
    tok[:T] = tokens
    pos = np.arange(Tp, dtype=np.int32)
    valid = np.arange(Tp) < T
    with jax.default_matmul_precision("highest"):
        hid = embed(weights["embed"], jnp.asarray(tok))
        gw = weights["groups"][0]
        for l in range(m.layers):
            hid = layer(hid, jnp.asarray(pos), jnp.asarray(valid), gw,
                        jnp.int32(l))
        nr = _bucket(len(rows), 64)
        r = np.zeros(nr, np.int32)
        r[:len(rows)] = rows
        out = head(hid, jnp.asarray(r), weights["final_norm"],
                   weights["unembed"])
    return np.asarray(out)[:len(rows), :m.vocab]


def served_gaps(weights, m, theta, eps, prompt: np.ndarray,
                served: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
    """Teacher-forced over ``prompt + served``: for each served token,
    the gap by which the reference's logit of that token lies below the
    reference's best, and the reference logits' rows (for the control).
    """
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    rows = list(range(len(prompt) - 1, len(prompt) - 1 + len(served)))
    ref = logits_at(weights, m, theta, eps, seq, rows)
    gaps = ref.max(-1) - ref[np.arange(len(served)), np.asarray(served)]
    return gaps, ref


def control_gaps(ref: np.ndarray, low: np.ndarray) -> np.ndarray:
    """The control's reading at each position: the gap, in the reference
    logits, of the token that the fp8 logits put first."""
    first = low.argmax(-1)
    return ref.max(-1) - ref[np.arange(len(first)), first]


def widest(gaps: List[np.ndarray]) -> float:
    return float(max((float(g.max()) for g in gaps if len(g)), default=0.0))


def mean(gaps: List[np.ndarray]) -> float:
    """The mean gap over every compared token: how often a token is not
    the reference's best, times by how much (both grow with the error,
    so the mean grows with its square)."""
    flat = np.concatenate([g for g in gaps if len(g)]) if gaps else \
        np.zeros(0)
    return float(flat.mean()) if flat.size else 0.0


def flips(gaps: List[np.ndarray]) -> int:
    return int(sum(int((g > 0).sum()) for g in gaps))
