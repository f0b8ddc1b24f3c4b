"""The attention backend layer: ONE registry-dispatched execution site
for every attention implementation in the stack.

Model code (``model._self_attention``) never inspects ``attn_impl``
again — it builds the merged KV view and calls :func:`attend`; the
string names a backend in :data:`BACKENDS` and that is the only
dispatch in the repository (CI greps for stray ``attn_impl ==``
ladders outside this module).

Dispatch contract
-----------------
Every backend is a callable

    ``fn(ctx, window, packed, q, k_all, v_all, kv_pos)
        -> (out, row_mass, key_mass)``

with ``q [B,Tq,H,D]``, ``k_all/v_all [B,S,Hkv,D]`` the *merged* KV
(cached slots + freshly scattered tokens), ``kv_pos [B,S]`` per-slot
absolute positions (-1 = dead slot), and ``ctx`` the model's ``Ctx``
(read-only). The contract bakes in the two serving-side invariants
that gate every backend identically under the packed==sequential
bit-equality harness:

* **per-request segment masks** — when ``packed`` (``ctx.seg_ids`` /
  ``ctx.kv_seg`` present) attention is confined to same-segment keys;
  the optional ``ctx.pack_qidx``/``pack_kidx`` gather maps switch the
  dense path to block-diagonal per-request attention without changing
  the numbers.
* **decode slots** — decode queries carry position -1 on masked batch
  rows (no live request); every backend must yield inert (zero) rows
  there, so incremental decode joins/leaves cannot perturb live rows.

``row_mass [B,Tq,C]`` / ``key_mass [B,S]`` are the Cache-Craft
attention statistics (None when not collected; the Pallas kernel path
never produces key-side mass — the capture falls back to inter-only
scoring).

Backends
--------
``dense``      position-mask + softmax oracle (block-diagonal when
               gather maps exist). The reference all others are
               gated against.
``kernel``     Pallas kernels: ``kernels/chunk_attention`` for
               prefill/partial windows (fused mass statistic, segment
               mask in-kernel) and ``kernels/decode_attention`` for
               single-token decode.
``sharded``    tensor-parallel dense under ``compat.shard_map`` on
               the serving mesh (see below).
``flash``      blocked online-softmax scan (``flash_skip``: balanced
               causal schedule, ``flash_cp``: context-parallel over
               the installed CP mesh).
``auto``       dense for small/stat-collecting/packed shapes, flash
               beyond ~2M score elements.
``paged``      block-table-native decode (see below).
``paged_kernel``  the Pallas paged-decode kernel over the same
               contract (numerics allclose, not bitwise — its online
               softmax reduces in block order).

Paged attend contract
---------------------
The ``paged`` backends read KV **in place from the KVPool's block
storage** instead of a gathered copy. The decode cache leaf is the
pool twin ``{"kp": [NBf, Hkv, D], "vp": [NBf, Hkv, D], "ppos":
[NBf]}`` — ``NBf = num_blocks * block_size`` flat arena slots shared
by every request — and the per-request view arrives through ``ctx``:

* ``ctx.paged_rows [B, S]`` — compact pool-flat slot-index rows
  (``KVPool.table_slot_index``): entry ``i`` is the arena slot holding
  the request's token at logical position ``i``, -1 pads. This is the
  ``(block_tables, context_lens)`` pair folded into one tensor: block
  ids appear as ``slot // block_size`` runs and the context length is
  the count of non-negative entries.
* ``ctx.paged_block_rows [B, NBmax]`` / ``ctx.paged_block_size`` —
  the raw block-id rows + block size for the Pallas kernel, whose
  scalar-prefetched index maps stream pool blocks directly (no
  device-side gather at all; per-slot ``ppos`` masking handles
  interior padding).
* ``k_all / v_all / kv_pos`` are the pool twin leaves themselves
  (3-d / 1-d instead of the dense contract's 4-d / 2-d) with the new
  token's KV already scattered at ``ctx.decode_slot``.

``paged`` dereferences the slot rows with a device-side gather and
delegates to the dense (or mesh-installed ``sharded``) oracle — the
gathered operand reproduces ``pool.gather(compact=True)``'s layout
element-for-element, so logits stay BIT-identical to the arena path
while the host-side arena copy (``decode_gather_bytes``) disappears.
``paged_kernel`` skips even that gather: the kernel walks the block
rows in place; head-sharded pools route each shard's ``kv_shards``
view through the same kernel under ``compat.shard_map``. Both yield
inert zero rows for masked slots (``decode_slot == -1``), like every
other backend.

Kernel tiling rule
------------------
The Pallas kernels run compiled on a TPU and in interpret mode on any
other backend (``repro.kernels.interpret_default``). Row tiles are
``min(block, round_up(rows, 8))`` and rows are padded up to whole tiles
(padding carries position -1), so a tiny-config CPU run executes the
real kernel body over a handful of tiles while every TPU tile stays
sublane-aligned; heads ride in the lane axis or as whole trailing
``[Hkv, D]`` blocks, never as a second-minor block of 1.

Head-shard KV layout invariants
-------------------------------
``sharded`` partitions q/k/v over the head axis of a ``("heads",)``
mesh installed via :func:`set_serving_mesh`; the KVPool mirrors the
same split (``kv_shards``) so each device owns ``Hkv / n`` contiguous
KV heads of every block:

* ``num_heads % n == 0`` and ``num_kv_heads % n == 0`` — contiguous
  head blocks keep the GQA q-head -> kv-head grouping shard-local, so
  per-head math is *bitwise* identical to the single-device oracle.
* the attention output is all-gathered (arithmetic-free) before the
  ``wo`` projection, keeping sharded == single-device logits exact;
  only the summed mass statistics cross shards (``psum``).
* block bookkeeping (free lists, refcounts, reservations, CoW) stays
  shard-agnostic: a block is allocated on every shard or none, so the
  pool-wide conservation law ``free + live + reserved == num_blocks``
  holds *per shard* by construction, and chunkstore residency,
  zero-copy shared runs and preemption reclaim run unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L

# ---------------------------------------------------------------------------
# Module-level mesh state (installed by launch/serving code before tracing)
# ---------------------------------------------------------------------------
_CP_MESH = None
_SERVING_MESH = None
_SERVING_AXIS = "heads"


def set_cp_mesh(mesh):
    """Install the mesh for context-parallel attention (attn_impl
    "flash_cp"); call from launch code before lowering."""
    global _CP_MESH
    _CP_MESH = mesh


def set_serving_mesh(mesh, axis: str = "heads"):
    """Install the tensor-parallel serving mesh for the ``sharded``
    backend (None uninstalls). Must be called before the first trace of
    a jit root that uses it — the mesh is read at trace time."""
    global _SERVING_MESH, _SERVING_AXIS
    _SERVING_MESH = mesh
    _SERVING_AXIS = axis


def serving_mesh():
    return _SERVING_MESH


# ---------------------------------------------------------------------------
# Pure array helpers shared by dense / sharded (shard_map bodies must be
# pure functions of arrays, so these take no Ctx)
# ---------------------------------------------------------------------------
def _dense_full(cfg, window, q, k_all, v_all, kv_pos, positions,
                q_seg, k_seg, k_chunk):
    mask = L.position_mask(positions, kv_pos, window,
                           q_seg=q_seg, k_seg=k_seg)
    return L.gqa_attend_dense(q, k_all, v_all, mask, k_chunk=k_chunk,
                              num_chunks=cfg.stats_chunks)


def _block_diagonal(cfg, window, q, k_all, v_all, kv_pos, positions,
                    k_chunk, qidx, kidx):
    """Packed-prefill attention without the cross-request quadratic
    waste: gather each request's query rows [R, Amax] and KV slice
    [R, Smax] (indices from the executor, -1 = padding), run batched
    dense attention per request, and scatter results back to the packed
    row order. Cost is R * Amax * Smax instead of (sum A)(sum S); the
    segment mask is implied by the block structure."""
    B, A = q.shape[:2]
    S = k_all.shape[1]
    R, Amax = qidx.shape
    Smax = kidx.shape[1]
    qsafe = jnp.clip(qidx, 0, A - 1)
    ksafe = jnp.clip(kidx, 0, S - 1)
    qr = q[0][qsafe]                                    # [R, Amax, H, D]
    kr = k_all[0][ksafe]                                # [R, Smax, Hkv, D]
    vr = v_all[0][ksafe]
    qpos_r = jnp.where(qidx >= 0, positions[0][qsafe], -1)
    kpos_r = jnp.where(kidx >= 0, kv_pos[0][ksafe], -1)
    mask = L.position_mask(qpos_r, kpos_r, window)
    k_chunk_r = None
    if k_chunk is not None:
        k_chunk_r = jnp.where(kidx >= 0, k_chunk[0][ksafe],
                              cfg.stats_chunks - 1)
    out_r, row_mass_r, key_mass_r = L.gqa_attend_dense(
        qr, kr, vr, mask, k_chunk=k_chunk_r,
        num_chunks=cfg.stats_chunks)
    # scatter back (each live row/slot appears exactly once; padding
    # lands in a dump slot that is sliced away)
    qflat = jnp.where(qidx >= 0, qidx, A).reshape(-1)
    H, D = out_r.shape[-2:]
    out = jnp.zeros((A + 1, H, D), out_r.dtype) \
        .at[qflat].set(out_r.reshape(-1, H, D))[:A][None]
    row_mass = key_mass = None
    if row_mass_r is not None:
        C = row_mass_r.shape[-1]
        row_mass = jnp.zeros((A + 1, C), row_mass_r.dtype) \
            .at[qflat].set(row_mass_r.reshape(-1, C))[:A][None]
    if key_mass_r is not None:
        kflat = jnp.where(kidx >= 0, kidx, S).reshape(-1)
        key_mass = jnp.zeros((S + 1,), key_mass_r.dtype) \
            .at[kflat].set(key_mass_r.reshape(-1))[:S][None]
    return out, row_mass, key_mass


# ---------------------------------------------------------------------------
# Backend implementations
# ---------------------------------------------------------------------------
def _impl_dense(ctx, window, packed, q, k_all, v_all, kv_pos):
    cfg = ctx.cfg
    k_chunk = ctx.chunk_ids if ctx.collect_stats else None
    if packed and ctx.pack_qidx is not None and ctx.pack_kidx is not None:
        return _block_diagonal(cfg, window, q, k_all, v_all, kv_pos,
                               ctx.positions, k_chunk,
                               ctx.pack_qidx, ctx.pack_kidx)
    return _dense_full(cfg, window, q, k_all, v_all, kv_pos, ctx.positions,
                       ctx.seg_ids if packed else None,
                       ctx.kv_seg if packed else None, k_chunk)


def _flash(ctx, window, packed, q, k_all, v_all, kv_pos, causal_skip=False):
    if ctx.collect_stats or packed:
        # flash has no mass statistic / segment mask: stats collection
        # and packed rows fall back to the dense oracle
        return _impl_dense(ctx, window, packed, q, k_all, v_all, kv_pos)
    out = L.gqa_attend_flash(q, k_all, v_all, ctx.positions, kv_pos,
                             window, causal_skip=causal_skip)
    return out, None, None


def _impl_flash(ctx, window, packed, q, k_all, v_all, kv_pos):
    return _flash(ctx, window, packed, q, k_all, v_all, kv_pos)


def _impl_flash_skip(ctx, window, packed, q, k_all, v_all, kv_pos):
    return _flash(ctx, window, packed, q, k_all, v_all, kv_pos,
                  causal_skip=True)


def _impl_flash_cp(ctx, window, packed, q, k_all, v_all, kv_pos):
    if ctx.collect_stats or packed:
        return _impl_dense(ctx, window, packed, q, k_all, v_all, kv_pos)
    if _CP_MESH is None:
        return _flash(ctx, window, packed, q, k_all, v_all, kv_pos)
    out = L.gqa_attend_flash_cp(q, k_all, v_all, ctx.positions, kv_pos,
                                _CP_MESH, window)
    return out, None, None


def _impl_auto(ctx, window, packed, q, k_all, v_all, kv_pos):
    if ctx.collect_stats or packed or q.shape[1] * k_all.shape[1] <= (1 << 21):
        return _impl_dense(ctx, window, packed, q, k_all, v_all, kv_pos)
    return _flash(ctx, window, packed, q, k_all, v_all, kv_pos)


def _impl_kernel(ctx, window, packed, q, k_all, v_all, kv_pos):
    cfg = ctx.cfg
    if ctx.mode == "decode" and q.shape[1] == 1 and not ctx.collect_stats:
        # single-token step: the fused decode kernel (grid over KV
        # blocks; masked batch rows with q_pos = -1 yield zeros)
        from repro.kernels.decode_attention.ops import decode_attention
        out = decode_attention(q[:, 0], k_all, v_all, ctx.positions[:, 0],
                               kv_pos, window=window)
        return out[:, None], None, None
    # Pallas chunk-attention kernel path: fused mass statistic, with
    # the per-request segment mask threaded into the kernel.
    from repro.kernels.chunk_attention.ops import chunk_attention
    out, row_mass = chunk_attention(
        q, k_all, v_all, ctx.positions, kv_pos,
        ctx.chunk_ids if ctx.chunk_ids is not None
        else jnp.zeros(kv_pos.shape, jnp.int32),
        q_seg=ctx.seg_ids, k_seg=ctx.kv_seg,
        num_chunks=cfg.stats_chunks, window=window)
    if not ctx.collect_stats:
        row_mass = None
    # the fused kernel does not expose key-side received mass; the
    # executor's capture falls back to inter-only scoring
    # (token_total=None) when kstats stays zero
    return out, row_mass, None


def _impl_sharded(ctx, window, packed, q, k_all, v_all, kv_pos):
    mesh = _SERVING_MESH
    if mesh is None:
        # single-device fallback: identical numbers, no mesh required
        return _impl_dense(ctx, window, packed, q, k_all, v_all, kv_pos)
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    cfg = ctx.cfg
    ax = _SERVING_AXIS
    n = mesh.shape[ax]
    H, Hkv = q.shape[2], k_all.shape[2]
    if H % n or Hkv % n:
        raise ValueError(
            f"sharded backend needs num_heads ({H}) and num_kv_heads "
            f"({Hkv}) divisible by the '{ax}' mesh axis ({n}) so head "
            f"blocks keep the GQA grouping shard-local")
    has_stats = ctx.collect_stats and ctx.chunk_ids is not None
    k_chunk = ctx.chunk_ids if has_stats \
        else jnp.zeros(kv_pos.shape, jnp.int32)
    use_bd = packed and ctx.pack_qidx is not None \
        and ctx.pack_kidx is not None
    shard4 = P(None, None, ax, None)
    rep = P()

    def finish(out, row_mass, key_mass):
        # all-gather is pure data movement -> per-head outputs stay
        # bitwise identical to the single-device oracle; only the
        # head-summed mass statistics need a cross-shard reduction
        out = jax.lax.all_gather(out, ax, axis=2, tiled=True)
        if has_stats:
            return out, jax.lax.psum(row_mass, ax), \
                jax.lax.psum(key_mass, ax)
        return (out,)

    if use_bd:
        def body(qs, ks, vs, pos, kvp, cid, qi, ki):
            return finish(*_block_diagonal(
                cfg, window, qs, ks, vs, kvp, pos,
                cid if has_stats else None, qi, ki))
        operands = (q, k_all, v_all, ctx.positions, kv_pos, k_chunk,
                    ctx.pack_qidx, ctx.pack_kidx)
        in_specs = (shard4, shard4, shard4, rep, rep, rep, rep, rep)
    else:
        zq = ctx.seg_ids if packed else jnp.zeros_like(ctx.positions)
        zk = ctx.kv_seg if packed else jnp.zeros_like(kv_pos)

        def body(qs, ks, vs, pos, kvp, sq, sk, cid):
            return finish(*_dense_full(
                cfg, window, qs, ks, vs, kvp, pos,
                sq if packed else None, sk if packed else None,
                cid if has_stats else None))
        operands = (q, k_all, v_all, ctx.positions, kv_pos, zq, zk,
                    k_chunk)
        in_specs = (shard4, shard4, shard4, rep, rep, rep, rep, rep)

    out_specs = (rep, rep, rep) if has_stats else (rep,)
    f = shard_map(body, mesh=mesh, in_specs=in_specs,
                  out_specs=out_specs, axis_names={ax}, check_vma=False)
    res = f(*operands)
    if has_stats:
        return res
    return res[0], None, None


def _impl_paged(ctx, window, packed, q, k_all, v_all, kv_pos):
    """Block-table-native decode, exact route: dereference the compact
    slot-index rows with one device-side gather and hand the result to
    the dense / sharded oracle. The gathered operand is
    ``pool.gather(compact=True)`` element-for-element (zeros + pos -1
    in padding), so logits are bit-identical to the arena path — while
    no host-side arena copy exists to build, rebuild, or join."""
    if ctx.paged_rows is None or k_all.ndim != 3:
        # not a pool-twin cache (e.g. unit tests driving the backend
        # with dense operands): the dense oracle is the fallback
        return _impl_dense(ctx, window, packed, q, k_all, v_all, kv_pos)
    rows = ctx.paged_rows                                   # [B, S]
    valid = rows >= 0
    safe = jnp.where(valid, rows, 0)
    kg = jnp.where(valid[..., None, None], k_all[safe], 0)  # [B,S,Hkv,D]
    vg = jnp.where(valid[..., None, None], v_all[safe], 0)
    kvp = jnp.where(valid, kv_pos[safe], -1)                # [B, S]
    if _SERVING_MESH is not None:
        return _impl_sharded(ctx, window, packed, q, kg, vg, kvp)
    return _impl_dense(ctx, window, packed, q, kg, vg, kvp)


def _impl_paged_kernel(ctx, window, packed, q, k_all, v_all, kv_pos):
    """Block-table-native decode, Pallas route: the kernel's
    scalar-prefetched index maps walk each request's block-id row and
    read K/V straight out of the pool twin — no gather of any kind.
    Online softmax reduces in block order, so this route is allclose
    (not bitwise) to the oracle, mirroring ``kernel`` vs ``dense``.
    Outside a paged decode step (prefill windows, dense operands) the
    route stays on Pallas: it is the ``kernel`` backend."""
    if ctx.paged_block_rows is None or k_all.ndim != 3:
        return _impl_kernel(ctx, window, packed, q, k_all, v_all, kv_pos)
    if not ctx.paged_block_size or ctx.collect_stats:
        raise ValueError("paged_kernel decode needs paged_block_size and "
                         "no attention statistics")
    from repro.kernels.decode_attention.ops import paged_decode_attention
    bs = ctx.paged_block_size
    NBf = k_all.shape[0]
    kb = k_all.reshape(NBf // bs, bs, *k_all.shape[1:])
    vb = v_all.reshape(NBf // bs, bs, *v_all.shape[1:])
    pb = kv_pos.reshape(NBf // bs, bs)
    qd = q[:, 0]                                            # [B, H, D]
    qpos = ctx.positions[:, 0]
    rows = ctx.paged_block_rows
    mesh = _SERVING_MESH
    if mesh is None:
        out = paged_decode_attention(qd, kb, vb, pb, rows, qpos,
                                     window=window)
        return out[:, None], None, None
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    ax = _SERVING_AXIS
    n = mesh.shape[ax]
    H, Hkv = qd.shape[1], kb.shape[2]
    if H % n or Hkv % n:
        raise ValueError(
            f"paged_kernel needs num_heads ({H}) and num_kv_heads "
            f"({Hkv}) divisible by the '{ax}' mesh axis ({n})")

    def body(qs, ks, vs):
        # each shard runs the kernel over ITS kv_shards view of the
        # pool blocks; the output all-gather is pure data movement
        o = paged_decode_attention(qs, ks, vs, pb, rows, qpos,
                                   window=window)
        return jax.lax.all_gather(o, ax, axis=1, tiled=True)

    shard_kv = P(None, None, ax, None)
    out = shard_map(body, mesh=mesh,
                    in_specs=(P(None, ax, None), shard_kv, shard_kv),
                    out_specs=P(), axis_names={ax},
                    check_vma=False)(qd, kb, vb)
    return out[:, None], None, None


BACKENDS = {
    "auto": _impl_auto,
    "dense": _impl_dense,
    "kernel": _impl_kernel,
    "sharded": _impl_sharded,
    "flash": _impl_flash,
    "flash_skip": _impl_flash_skip,
    "flash_cp": _impl_flash_cp,
    "paged": _impl_paged,
    "paged_kernel": _impl_paged_kernel,
}


def attend(ctx, kind: str, q, k_all, v_all, kv_pos):
    """THE attention dispatch site. ``kind`` is the layer kind
    ("global" | "local"); everything else follows the contract above."""
    try:
        impl = BACKENDS[ctx.attn_impl]
    except KeyError:
        raise ValueError(
            f"unknown attn_impl {ctx.attn_impl!r}; known: "
            f"{sorted(BACKENDS)}") from None
    window = ctx.cfg.window if kind == "local" else 0
    packed = ctx.seg_ids is not None and ctx.kv_seg is not None
    return impl(ctx, window, packed, q, k_all, v_all, kv_pos)
