"""Unified decoder stack executing every assigned architecture family.

The stack is a ``lax.scan`` over repeating pattern groups (compile time
flat in depth), with four execution modes sharing one block implementation:

  mode="train"    full causal forward, remat, returns logits (+ MoE aux)
  mode="prefill"  full forward, returns per-layer KV/state cache (+ the
                  Cache-Craft attention statistics when requested)
  mode="partial"  Cache-Craft partial prefill: hidden states exist ONLY for
                  the active tokens (new chunks + recompute + question);
                  cached KV occupies its slots, fresh KV is scattered in,
                  and Q attends across the merged KV with a position mask
  mode="decode"   single-token step against the cache

Caches carry an explicit per-slot position array so causality is always
derived from absolute positions — the invariant that makes chunk-cache
reuse at arbitrary locations well-defined.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import shd
from repro.models import backend as AB
from repro.models import layers as L
from repro.models.config import ModelConfig

# launch code historically installs the context-parallel mesh through
# the model module; the state now lives in the backend layer
set_cp_mesh = AB.set_cp_mesh

PyTree = Any


# ---------------------------------------------------------------------------
# Parameter definitions: one source of truth for init, shapes and shardings
# ---------------------------------------------------------------------------
def _attn_defs(cfg: ModelConfig, cross: bool = False) -> Dict[str, tuple]:
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    kv_src = d
    defs = {
        "ln1": ((d,), ("embed",), "zero"),
        "wq": ((d, h, dh), ("embed", "heads", "q_head_dim"), "fan_in"),
        "wk": ((kv_src, hkv, dh), ("embed", "kv_heads", "kv_head_dim"),
               "fan_in"),
        "wv": ((kv_src, hkv, dh), ("embed", "kv_heads", "kv_head_dim"),
               "fan_in"),
        "wo": ((h, dh, d), ("heads", "q_head_dim", "embed"), "fan_in2"),
    }
    if cross:
        defs["gate_attn"] = ((), (), "zero")
        defs["gate_ffn"] = ((), (), "zero")
    return defs


def _ffn_defs(cfg: ModelConfig) -> Dict[str, tuple]:
    d, f = cfg.d_model, cfg.d_ff
    defs = {"ln2": ((d,), ("embed",), "zero")}
    if cfg.num_experts:
        e = cfg.num_experts
        defs["router"] = ((d, e), ("embed", None), "fan_in")
        defs["wi_e"] = ((e, d, 2, f), ("experts", "embed", None, "expert_mlp"),
                        "fan_in")
        defs["wo_e"] = ((e, f, d), ("experts", "expert_mlp", "embed"),
                        "fan_in")
    else:
        defs["wi"] = ((d, 2, f), ("embed", None, "mlp"), "fan_in")
        defs["wo_ff"] = ((f, d), ("mlp", "embed"), "fan_in")
    return defs


def _rglru_defs(cfg: ModelConfig) -> Dict[str, tuple]:
    d, r, w = cfg.d_model, cfg.rnn_width_, cfg.conv_width
    return {
        "ln1": ((d,), ("embed",), "zero"),
        "wx": ((d, r), ("embed", "rnn"), "fan_in"),
        "wy": ((d, r), ("embed", "rnn"), "fan_in"),
        "conv": ((w, r), (None, "rnn"), "fan_in"),
        "lam": ((r,), ("rnn",), "rglru_lambda"),
        "alpha": ((r,), ("rnn",), "one"),
        "beta": ((r,), ("rnn",), "one"),
        "wo_r": ((r, d), ("rnn", "embed"), "fan_in"),
    }


def _ssd_defs(cfg: ModelConfig) -> Dict[str, tuple]:
    d, di, ns, nh, w = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                        cfg.ssm_heads, cfg.conv_width)
    in_w = 2 * di + 2 * ns + nh
    return {
        "ln1": ((d,), ("embed",), "zero"),
        "in_proj": ((d, in_w), ("embed", None), "fan_in"),
        "conv": ((w, di), (None, "rnn"), "fan_in"),
        "A_log": ((nh,), ("ssm_heads",), "ssd_a"),
        "D": ((nh,), ("ssm_heads",), "one"),
        "dt_bias": ((nh,), ("ssm_heads",), "zero"),
        "out_norm": ((di,), ("rnn",), "zero"),
        "out_proj": ((di, d), ("rnn", "embed"), "fan_in"),
    }


def _kind_defs(cfg: ModelConfig, kind: str) -> Dict[str, tuple]:
    if kind in ("attn", "local"):
        return {**_attn_defs(cfg), **_ffn_defs(cfg)}
    if kind == "xattn":
        return {**_attn_defs(cfg, cross=True), **_ffn_defs(cfg)}
    if kind == "rglru":
        return {**_rglru_defs(cfg), **_ffn_defs(cfg)}
    if kind == "ssd":
        return _ssd_defs(cfg)
    raise ValueError(kind)


def _init_leaf(key, shape, init, dtype):
    if init == "zero" or not shape:
        return jnp.zeros(shape, dtype)
    if init == "one":
        return jnp.ones(shape, dtype)
    if init == "rglru_lambda":  # a in (0.9, 0.999) after softplus mapping
        u = jax.random.uniform(key, shape, jnp.float32, 0.35, 0.65)
        return jnp.log(jnp.expm1(-jnp.log(u) / L._RGLRU_C)).astype(dtype)
    if init == "ssd_a":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          1.0, 8.0)).astype(dtype)
    fan_in = shape[0] if init == "fan_in" else int(np.prod(shape[:-1]))
    if init == "fan_in" and len(shape) > 1:
        fan_in = shape[0]
    scale = 1.0 / np.sqrt(max(1, fan_in if init != "fan_in2"
                              else int(np.prod(shape[:2]))))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array) -> PyTree:
    dtype = jnp.dtype(cfg.param_dtype)
    d, vp = cfg.d_model, cfg.padded_vocab
    keys = iter(jax.random.split(key, 4 + 2 * cfg.num_layers * 16))

    def make(defs):
        return {n: _init_leaf(next(keys), s, i, dtype)
                for n, (s, _, i) in defs.items()}

    pattern = cfg.pattern
    groups = []
    for p, kind in enumerate(pattern):
        defs = _kind_defs(cfg, kind)
        # keys drawn group-major (as make(defs) per group would), but
        # stacked leaf by leaf: at full width only one leaf's per-group
        # draws are alive next to the stack, not a second copy of all
        gkeys = [[next(keys) for _ in defs] for _ in range(cfg.n_groups)]
        groups.append({
            n: jnp.stack([_init_leaf(gk[j], s, i, dtype) for gk in gkeys])
            for j, (n, (s, _, i)) in enumerate(defs.items())}
            if cfg.n_groups else {})
    tail = [make(_kind_defs(cfg, cfg.layer_kinds[cfg.n_groups * len(pattern)
                                                 + i]))
            for i in range(cfg.n_tail)]
    return {
        "embed": (jax.random.normal(next(keys), (vp, d), jnp.float32)
                  * 0.02).astype(dtype),
        "unembed": _init_leaf(next(keys), (d, vp), "fan_in", dtype),
        "final_norm": jnp.zeros((d,), dtype),
        "groups": groups,
        "tail": tail,
    }


def param_axes(cfg: ModelConfig) -> PyTree:
    def axes(defs):
        return {n: a for n, (_, a, _) in defs.items()}

    pattern = cfg.pattern
    groups = []
    for p, kind in enumerate(pattern):
        base = axes(_kind_defs(cfg, kind))
        groups.append({n: (None,) + a for n, a in base.items()})
    tail = [axes(_kind_defs(cfg, cfg.layer_kinds[cfg.n_groups * len(pattern)
                                                 + i]))
            for i in range(cfg.n_tail)]
    return {
        "embed": ("vocab", "embed"),
        "unembed": ("embed", "vocab"),
        "final_norm": ("embed",),
        "groups": groups,
        "tail": tail,
    }


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------
def _kv_len(cfg: ModelConfig, kind: str, seq_len: int,
            ring: bool = True) -> int:
    if kind == "local" and ring:
        return min(seq_len, cfg.window)
    return seq_len


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                     dtype, ring: bool = True) -> Dict[str, jax.Array]:
    hkv, dh = cfg.num_kv_heads, cfg.head_dim_
    if kind in ("attn", "local"):
        s = _kv_len(cfg, kind, seq_len, ring)
        return {
            "k": jnp.zeros((batch, s, hkv, dh), dtype),
            "v": jnp.zeros((batch, s, hkv, dh), dtype),
            "pos": jnp.full((batch, s), -1, jnp.int32),
        }
    if kind == "xattn":
        m = cfg.num_media_tokens
        return {
            "mk": jnp.zeros((batch, m, hkv, dh), dtype),
            "mv": jnp.zeros((batch, m, hkv, dh), dtype),
        }
    if kind == "rglru":
        r, w = cfg.rnn_width_, cfg.conv_width
        return {
            "h": jnp.zeros((batch, r), jnp.float32),
            "conv": jnp.zeros((batch, w - 1, r), dtype),
        }
    if kind == "ssd":
        return {
            "s": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                            cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.d_inner),
                              dtype),
        }
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               dtype=None, ring: bool = True) -> PyTree:
    dtype = jnp.dtype(dtype or cfg.dtype)
    pattern = cfg.pattern

    def stack(kind):
        one = init_layer_cache(cfg, kind, batch, seq_len, dtype, ring)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_groups,) + x.shape)
            if x.ndim else x, one)

    groups = [stack(k) for k in pattern] if cfg.n_groups else []
    tail = [init_layer_cache(cfg, cfg.layer_kinds[cfg.n_groups *
                                                  len(pattern) + i],
                             batch, seq_len, dtype, ring)
            for i in range(cfg.n_tail)]
    return {"groups": groups, "tail": tail}


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------
@dataclass
class Ctx:
    cfg: ModelConfig
    mode: str                      # train | prefill | partial | decode
    positions: jax.Array           # [B,T] positions of the carried tokens
    media: Optional[jax.Array] = None
    chunk_ids: Optional[jax.Array] = None   # [B,T] per-token chunk id
    collect_stats: bool = False
    attn_impl: str = "auto"        # backend name, see backend.BACKENDS
    decode_slot: Optional[jax.Array] = None  # [B] write slot for decode
    # --- packed multi-request prefill (mode="partial") -------------------
    # Several requests share one sequence row: each token carries a
    # request-local position (RoPE / causality), a cache *slot* (request
    # layout offset + local position), and a segment id; attention is
    # confined to same-segment keys via the position mask.
    slots: Optional[jax.Array] = None        # [B,T] cache write slots
    seg_ids: Optional[jax.Array] = None      # [B,T] query segment ids
    kv_seg: Optional[jax.Array] = None       # [B,S] cache-slot segment ids
    # Block-diagonal gather maps (dense path): row/slot indices of each
    # request's tokens (-1 padding). Attention then runs per request on
    # [R, Amax] x [R, Smax] slices instead of the full [A, S] product —
    # the packed pass keeps linear ops fused without paying the
    # cross-request quadratic attention waste.
    pack_qidx: Optional[jax.Array] = None    # [R, Amax] -> packed q rows
    pack_kidx: Optional[jax.Array] = None    # [R, Smax] -> packed kv slots
    # --- paged decode (pool-twin cache leaves {"kp","vp","ppos"}) --------
    # Per-request views over the shared flat pool arena; see the paged
    # attend contract in models/backend.py. decode_slot then carries
    # pool-FLAT slot ids (block * block_size + offset).
    paged_rows: Optional[jax.Array] = None        # [B,S] slot-index rows
    paged_block_rows: Optional[jax.Array] = None  # [B,NBmax] block rows
    paged_block_size: int = 0                     # pool block size (static)


def _self_attention(ctx: Ctx, kind: str, p, x, state):
    cfg = ctx.cfg
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    q = shd(q, "batch", None, "attn_q", "attn_dim")
    k = shd(k, "batch", None, "attn_kv", "attn_dim")
    v = shd(v, "batch", None, "attn_kv", "attn_dim")
    q = L.apply_rope(q, ctx.positions, cfg.rope_theta)
    k = L.apply_rope(k, ctx.positions, cfg.rope_theta)

    new_state = state
    B, T = x.shape[:2]
    bi = jnp.arange(B)[:, None]
    if ctx.mode == "train":
        k_all, v_all, kv_pos = k, v, ctx.positions
    elif ctx.mode in ("prefill", "partial"):
        s_cache = state["k"].shape[1]
        if kind == "local" and s_cache < T:
            # Ring cache smaller than the prompt (decode-oriented alloc):
            # deterministically keep the last `window` tokens at slot
            # pos % window; attention itself runs over the fresh full KV.
            w = s_cache
            slot = ctx.positions[:, -w:] % w
            new_state = {
                "k": state["k"].at[bi, slot].set(k[:, -w:]),
                "v": state["v"].at[bi, slot].set(v[:, -w:]),
                "pos": state["pos"].at[bi, slot].set(
                    ctx.positions[:, -w:]),
            }
            k_all, v_all, kv_pos = k, v, ctx.positions
        else:
            # Scatter fresh KV into the (possibly pre-populated) cache at
            # absolute positions; padding positions (-1) become OOB drops.
            # Packed multi-request prefill supplies explicit write slots
            # (request layout offset + local position) via ctx.slots.
            wpos = ctx.slots if ctx.slots is not None else ctx.positions
            slot = jnp.where(wpos >= 0, wpos, s_cache)
            k_all = state["k"].at[bi, slot].set(k, mode="drop")
            v_all = state["v"].at[bi, slot].set(v, mode="drop")
            kv_pos = state["pos"].at[bi, slot].set(
                ctx.positions, mode="drop")
            new_state = {"k": k_all, "v": v_all, "pos": kv_pos}
            # attention must read the merged KV head-sharded/replicated,
            # not contraction(D)-sharded (cache storage layout)
            k_all = shd(k_all, "batch", None, "attn_kv", "attn_dim")
            v_all = shd(v_all, "batch", None, "attn_kv", "attn_dim")
    elif ctx.mode == "decode" and "kp" in state:
        # Paged decode: the cache leaf is the pool twin (flat arena
        # slots shared by every request, no batch axis). decode_slot
        # carries pool-FLAT slot ids; masked rows (-1) drop the write
        # and their query position (-1) masks all attention. Distinct
        # live requests own distinct slots by pool construction.
        nslots = state["kp"].shape[0]
        wslot = jnp.where(ctx.decode_slot >= 0, ctx.decode_slot, nslots)
        k_all = state["kp"].at[wslot].set(k[:, 0], mode="drop")
        v_all = state["vp"].at[wslot].set(v[:, 0], mode="drop")
        kv_pos = state["ppos"].at[wslot].set(ctx.positions[:, 0],
                                             mode="drop")
        new_state = {"kp": k_all, "vp": v_all, "ppos": kv_pos}
    elif ctx.mode == "decode":
        # Masked batch rows (no live request in the row) carry slot = -1
        # and position = -1: the KV write drops entirely and the row's
        # query position masks all attention, so a dead row is inert.
        slot = ctx.decode_slot[:, None]
        if kind == "local":
            slot = jnp.where(slot >= 0, slot % state["k"].shape[1], slot)
        s_cache = state["k"].shape[1]
        slot = jnp.where(slot >= 0, slot, s_cache)
        k_all = state["k"].at[bi, slot].set(k, mode="drop")
        v_all = state["v"].at[bi, slot].set(v, mode="drop")
        kv_pos = state["pos"].at[bi, slot].set(ctx.positions, mode="drop")
        new_state = {"k": k_all, "v": v_all, "pos": kv_pos}
    else:
        raise ValueError(ctx.mode)

    out, row_mass, key_mass = AB.attend(ctx, kind, q, k_all, v_all, kv_pos)
    # pin the attention interior: without this, a model-sharded wo
    # head_dim pulls D-sharding back INTO the flash loop and every score
    # tile becomes a partial-sum all-reduce
    out = shd(out, "batch", None, "attn_q", "attn_dim")
    # bf16 out-projection so the TP all-reduce is not f32 (see swiglu)
    out = jnp.einsum("bthk,hkd->btd", out, p["wo"],
                     preferred_element_type=out.dtype)
    return out, new_state, row_mass, key_mass


def _cross_attention(ctx: Ctx, p, x, state):
    cfg = ctx.cfg
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    if ctx.mode in ("train", "prefill", "partial") and ctx.media is not None:
        mk = jnp.einsum("bmd,dhk->bmhk", ctx.media, p["wk"])
        mv = jnp.einsum("bmd,dhk->bmhk", ctx.media, p["wv"])
        if state is not None:
            state = {"mk": mk, "mv": mv}
    else:
        mk, mv = state["mk"], state["mv"]
    B, Tq = q.shape[:2]
    mask = jnp.ones((B, Tq, mk.shape[1]), bool)
    if Tq * mk.shape[1] <= (1 << 21):
        out = L.gqa_attend_dense(q, mk, mv, mask)[0]
    else:
        out = L.gqa_attend_flash(q, mk, mv,
                                 jnp.ones((B, Tq), jnp.int32),
                                 jnp.zeros((B, mk.shape[1]), jnp.int32))
    out = shd(out, "batch", None, "attn_q", "attn_dim")
    out = jnp.einsum("bthk,hkd->btd", out, p["wo"])
    return jnp.tanh(p["gate_attn"]) * out, state


def _ffn(ctx: Ctx, p, x):
    cfg = ctx.cfg
    if cfg.num_experts:
        out, probs = L.moe_ffn(x, p["router"], p["wi_e"], p["wo_e"],
                               experts_per_token=cfg.experts_per_token,
                               capacity_factor=cfg.capacity_factor)
        aux = L.moe_aux_loss(probs, cfg.num_experts)
        return out, aux
    return L.swiglu(x, p["wi"], p["wo_ff"]), jnp.float32(0.0)


def _rglru_block(ctx: Ctx, p, x, state):
    cfg = ctx.cfg
    gate = jax.nn.gelu(jnp.einsum("btd,dr->btr", x, p["wy"]))
    b = jnp.einsum("btd,dr->btr", x, p["wx"])
    b = shd(b, "batch", None, "rnn")
    conv_state = state["conv"] if (state is not None and
                                   ctx.mode in ("decode",)) else None
    b, new_conv = L.causal_conv1d(b, p["conv"], conv_state)
    if ctx.mode == "decode":
        y, h = L.rglru_step(b[:, 0], p["lam"], p["alpha"], p["beta"],
                            state["h"])
        y = y[:, None]
    else:
        h0 = state["h"] if (state is not None and ctx.mode == "partial") \
            else None
        y, h = L.rglru_scan(b, p["lam"], p["alpha"], p["beta"], h0)
    out = jnp.einsum("btr,rd->btd", gate * y, p["wo_r"])
    new_state = None
    if state is not None:
        new_state = {"h": h.astype(jnp.float32), "conv": new_conv}
    return out, new_state


def _ssd_block(ctx: Ctx, p, x, state):
    cfg = ctx.cfg
    di, ns, nh, pd = (cfg.d_inner, cfg.ssm_state, cfg.ssm_heads,
                      cfg.ssm_head_dim)
    proj = jnp.einsum("btd,dw->btw", x, p["in_proj"])
    z, xs, Bm, Cm, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + ns, 2 * di + 2 * ns], axis=-1)
    xs = shd(xs, "batch", None, "rnn")
    conv_state = state["conv"] if (state is not None and
                                   ctx.mode == "decode") else None
    xs, new_conv = L.causal_conv1d(xs, p["conv"], conv_state)
    xs = jax.nn.silu(xs)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    B_, T = xs.shape[0], xs.shape[1]
    xh = xs.reshape(B_, T, nh, pd)
    if ctx.mode == "decode":
        y, s = L.ssd_step(xh[:, 0], dt[:, 0], p["A_log"], Bm[:, 0], Cm[:, 0],
                          p["D"], state["s"])
        y = y[:, None]
    else:
        s0 = state["s"] if (state is not None and ctx.mode == "partial") \
            else None
        y, s = L.ssd_chunked(xh, dt, p["A_log"], Bm, Cm, p["D"],
                             cfg.ssd_chunk, s0)
    y = y.reshape(B_, T, di)
    y = L.rms_norm(y, p["out_norm"], cfg.norm_eps) * jax.nn.silu(z)
    out = jnp.einsum("bti,id->btd", y, p["out_proj"])
    new_state = None
    if state is not None:
        new_state = {"s": s.astype(jnp.float32), "conv": new_conv}
    return out, new_state


def apply_block(ctx: Ctx, kind: str, p, h, state):
    cfg = ctx.cfg
    x = L.rms_norm(h, p["ln1"], cfg.norm_eps)
    aux = jnp.float32(0.0)
    row_mass = jnp.zeros(
        (h.shape[0], h.shape[1], cfg.stats_chunks), jnp.float32)
    key_mass = jnp.zeros((h.shape[0], h.shape[1]), jnp.float32)
    if kind in ("attn", "local"):
        out, state, rm, km = _self_attention(ctx, kind, p, x, state)
        if rm is not None:
            row_mass = rm
        if km is not None and km.shape == key_mass.shape:
            key_mass = km
        h = h + out
        y, aux = _ffn(ctx, p, L.rms_norm(h, p["ln2"], cfg.norm_eps))
        h = h + y
    elif kind == "xattn":
        out, state = _cross_attention(ctx, p, x, state)
        h = h + out
        y, aux = _ffn(ctx, p, L.rms_norm(h, p["ln2"], cfg.norm_eps))
        h = h + jnp.tanh(p["gate_ffn"]) * y
    elif kind == "rglru":
        out, state = _rglru_block(ctx, p, x, state)
        h = h + out
        y, aux = _ffn(ctx, p, L.rms_norm(h, p["ln2"], cfg.norm_eps))
        h = h + y
    elif kind == "ssd":
        out, state = _ssd_block(ctx, p, x, state)
        h = h + out
    else:
        raise ValueError(kind)
    h = shd(h, "batch", "seq", "embed")
    return h, state, row_mass, key_mass, aux


# ---------------------------------------------------------------------------
# Full stack
# ---------------------------------------------------------------------------
@dataclass
class ModelOutput:
    logits: jax.Array
    cache: Optional[PyTree] = None
    stats: Optional[jax.Array] = None       # [L, B, T, C] row chunk mass
    key_stats: Optional[jax.Array] = None   # [L, B, T] mass received per key
    aux_loss: jax.Array = 0.0
    hidden: Optional[jax.Array] = None


def embed_tokens(cfg: ModelConfig, params: PyTree, tokens: jax.Array):
    tokens = shd(tokens, "batch", None)
    return params["embed"].astype(jnp.dtype(cfg.dtype))[tokens]


def lm_head(cfg: ModelConfig, params: PyTree, h: jax.Array):
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", h,
                        params["unembed"].astype(jnp.dtype(cfg.dtype)))
    return shd(logits, "batch", "seq", "vocab")


def run_stack(cfg: ModelConfig, params: PyTree, h: jax.Array, ctx: Ctx,
              cache: Optional[PyTree] = None, collect_stats: bool = False,
              g0: int = 0, g1: Optional[int] = None, tail: bool = True):
    """Apply layer groups [g0, g1) (+ optional tail) to hidden states h.

    Returns (h, new_cache_slice, stats [Lwindow,B,T,C] | None, aux).
    ``cache`` must be sliced consistently with (g0, g1, tail)."""
    pattern = cfg.pattern
    g1 = cfg.n_groups if g1 is None else g1
    want_cache = cache is not None

    def body(h, params_g, states_g):
        new_states, masses, kmasses, aux_t = [], [], [], jnp.float32(0.0)
        for pi, kind in enumerate(pattern):
            st = states_g[pi] if states_g is not None else None
            h, st, rm, km, aux = apply_block(ctx, kind, params_g[pi], h, st)
            new_states.append(st)
            masses.append(rm)
            kmasses.append(km)
            aux_t = aux_t + aux
        return h, new_states, masses, kmasses, aux_t

    body_fn = body
    if cfg.remat and ctx.mode == "train":
        body_fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    stats_list, kstats_list, aux_total = [], [], jnp.float32(0.0)
    new_cache = {"groups": [], "tail": []}
    if g1 > g0:
        def scan_body(carry_h, xs):
            params_g, states_g = xs
            h2, sts, masses, kmasses, aux = body_fn(carry_h, params_g,
                                                    states_g)
            ys = (sts if want_cache else [None] * len(pattern),
                  jnp.stack(masses) if collect_stats else jnp.float32(0.0),
                  jnp.stack(kmasses) if collect_stats else jnp.float32(0.0),
                  aux)
            return h2, ys

        params_w = jax.tree.map(lambda x: x[g0:g1], params["groups"])
        cache_w = None
        if want_cache:
            cache_w = jax.tree.map(lambda x: x[g0:g1], cache["groups"])
        h, (sts, masses, kmasses, auxes) = jax.lax.scan(scan_body, h,
                                                        (params_w, cache_w))
        if want_cache:
            new_cache["groups"] = sts
        if collect_stats:
            # masses [n_groups, P, B, T, C] -> [L_window, B, T, C]
            stats_list.append(masses.reshape((-1,) + masses.shape[2:]))
            kstats_list.append(kmasses.reshape((-1,) + kmasses.shape[2:]))
        aux_total = aux_total + jnp.sum(auxes)

    if tail:
        for i in range(cfg.n_tail):
            kind = cfg.layer_kinds[cfg.n_groups * len(pattern) + i]
            st = cache["tail"][i] if want_cache else None
            h, st, rm, km, aux = apply_block(ctx, kind, params["tail"][i],
                                             h, st)
            if want_cache:
                new_cache["tail"].append(st)
            if collect_stats:
                stats_list.append(rm[None])
                kstats_list.append(km[None])
            aux_total = aux_total + aux

    stats = jnp.concatenate(stats_list, axis=0) if collect_stats else None
    kstats = jnp.concatenate(kstats_list, axis=0) if collect_stats else None
    return h, (new_cache if want_cache else None), stats, kstats, aux_total


def forward(cfg: ModelConfig, params: PyTree, *,
            tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            media: Optional[jax.Array] = None,
            positions: Optional[jax.Array] = None,
            mode: str = "train",
            cache: Optional[PyTree] = None,
            chunk_ids: Optional[jax.Array] = None,
            collect_stats: bool = False,
            attn_impl: str = "auto",
            decode_slot: Optional[jax.Array] = None,
            slots: Optional[jax.Array] = None,
            seg_ids: Optional[jax.Array] = None,
            kv_seg: Optional[jax.Array] = None,
            paged_rows: Optional[jax.Array] = None,
            paged_block_rows: Optional[jax.Array] = None,
            paged_block_size: int = 0,
            logits_slice: str = "all") -> ModelOutput:
    dtype = jnp.dtype(cfg.dtype)
    if embeds is None:
        h = embed_tokens(cfg, params, tokens)
    else:
        h = embeds.astype(dtype)
    if h.ndim == 2:
        h = h[:, None]
    B, T = h.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    h = shd(h, "batch", "seq", "embed")
    media = None if media is None else media.astype(dtype)

    ctx = Ctx(cfg=cfg, mode=mode, positions=positions, media=media,
              chunk_ids=chunk_ids, collect_stats=collect_stats,
              attn_impl=attn_impl, decode_slot=decode_slot,
              slots=slots, seg_ids=seg_ids, kv_seg=kv_seg,
              paged_rows=paged_rows, paged_block_rows=paged_block_rows,
              paged_block_size=paged_block_size)
    h, new_cache, stats, kstats, aux_total = run_stack(
        cfg, params, h, ctx, cache=cache, collect_stats=collect_stats)

    if logits_slice == "last":
        h = h[:, -1:]
    logits = lm_head(cfg, params, h)
    return ModelOutput(logits=logits, cache=new_cache,
                       stats=stats, key_stats=kstats, aux_loss=aux_total,
                       hidden=h)


# Convenience entry points ---------------------------------------------------
def prefill(cfg, params, tokens=None, embeds=None, media=None,
            positions=None, chunk_ids=None, collect_stats=False,
            attn_impl="auto", cache_len: Optional[int] = None,
            ring: bool = True):
    B = (tokens if tokens is not None else embeds).shape[0]
    T = (tokens if tokens is not None else embeds).shape[1]
    cache = init_cache(cfg, B, cache_len or T, ring=ring)
    return forward(cfg, params, tokens=tokens, embeds=embeds, media=media,
                   positions=positions, mode="prefill", cache=cache,
                   chunk_ids=chunk_ids, collect_stats=collect_stats,
                   attn_impl=attn_impl)


def partial_prefill(cfg, params, tokens, positions, cache, media=None,
                    chunk_ids=None, collect_stats=False, attn_impl="auto",
                    embeds=None):
    return forward(cfg, params, tokens=tokens, embeds=embeds, media=media,
                   positions=positions, mode="partial", cache=cache,
                   chunk_ids=chunk_ids, collect_stats=collect_stats,
                   attn_impl=attn_impl)


def decode_step(cfg, params, tokens, positions, cache, decode_slot=None,
                attn_impl="auto", paged_rows=None, paged_block_rows=None,
                paged_block_size=0):
    """tokens [B], positions [B] -> logits [B,1,V] + updated cache."""
    if decode_slot is None:
        decode_slot = positions
    return forward(cfg, params, tokens=tokens[:, None],
                   positions=positions[:, None], mode="decode", cache=cache,
                   decode_slot=decode_slot, attn_impl=attn_impl,
                   paged_rows=paged_rows, paged_block_rows=paged_block_rows,
                   paged_block_size=paged_block_size, logits_slice="last")
