"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret
mode on CPU), including hypothesis property tests on shapes/dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the whole module is the interpret-mode kernel matrix job in CI
# (`-m kernel_interpret`, continue-on-error until CPU interpret cost is
# resolved; the tier1 job deselects the marker so the soft gate is the
# only CI gate on these). Default local runs still include it.
pytestmark = pytest.mark.kernel_interpret
from hypothesis import given
from hypothesis import strategies as st

from repro.kernels.chunk_attention.ops import chunk_attention
from repro.kernels.chunk_attention.ref import chunk_attention_ref
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.rope.ops import rope
from repro.kernels.rope.ref import rope_ref
from repro.kernels.ssd.ops import ssd_intra
from repro.kernels.ssd.ref import ssd_intra_ref


def _mk(rng, *shape, dtype=np.float32):
    return jnp.asarray(rng.normal(size=shape).astype(dtype))


# ---------------- chunk attention -------------------------------------------
@pytest.mark.parametrize("A,S,H,Hkv,D,C,window", [
    (16, 64, 4, 4, 32, 8, 0),       # MHA
    (48, 160, 8, 4, 32, 8, 0),      # GQA
    (32, 96, 8, 2, 64, 16, 0),      # deep GQA
    (32, 96, 4, 2, 32, 8, 48),      # sliding window
    (8, 32, 4, 1, 128, 4, 0),       # MQA, wide head
])
def test_chunk_attention_vs_ref(rng, A, S, H, Hkv, D, C, window):
    q = _mk(rng, A, H, D)
    k = _mk(rng, S, Hkv, D)
    v = _mk(rng, S, Hkv, D)
    qpos = np.sort(rng.choice(S, A, replace=False)).astype(np.int32)
    kpos = np.arange(S, dtype=np.int32)
    kpos[-S // 8:] = -1
    kch = np.minimum(np.maximum(kpos, 0) * C // S, C - 1).astype(np.int32)
    o, m = chunk_attention(q, k, v, jnp.asarray(qpos), jnp.asarray(kpos),
                           jnp.asarray(kch), num_chunks=C, window=window,
                           block_q=16, block_k=32)
    oref, mref = chunk_attention_ref(q, k, v, jnp.asarray(qpos),
                                     jnp.asarray(kpos), jnp.asarray(kch),
                                     num_chunks=C, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mref),
                               rtol=3e-5, atol=3e-5)


def test_chunk_attention_bf16(rng):
    A, S, H, Hkv, D, C = 16, 64, 4, 2, 32, 8
    q = _mk(rng, A, H, D).astype(jnp.bfloat16)
    k = _mk(rng, S, Hkv, D).astype(jnp.bfloat16)
    v = _mk(rng, S, Hkv, D).astype(jnp.bfloat16)
    qpos = jnp.asarray(np.arange(A) * 2, jnp.int32)
    kpos = jnp.asarray(np.arange(S), jnp.int32)
    kch = jnp.asarray(np.arange(S) // 8 % C, jnp.int32)
    o, m = chunk_attention(q, k, v, qpos, kpos, kch, num_chunks=C,
                           block_q=16, block_k=32)
    oref, mref = chunk_attention_ref(q, k, v, qpos, kpos, kch, num_chunks=C)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(oref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_chunk_attention_segment_mask(rng):
    """Packed multi-request masking: a kernel call over two packed
    segments must equal (a) the oracle with the same seg ids and (b) two
    independent per-segment kernel calls."""
    H, Hkv, D, C = 4, 2, 32, 8
    A1, S1, A2, S2 = 16, 48, 8, 32
    q = _mk(rng, A1 + A2, H, D)
    k = _mk(rng, S1 + S2, Hkv, D)
    v = _mk(rng, S1 + S2, Hkv, D)
    # request-local positions restart at 0 for the second segment
    qpos = np.concatenate([np.arange(A1) * 2, np.arange(A2) * 3])
    kpos = np.concatenate([np.arange(S1), np.arange(S2)]).astype(np.int32)
    kch = np.concatenate([np.arange(S1) % C, np.arange(S2) % C])
    qseg = np.concatenate([np.zeros(A1), np.ones(A2)]).astype(np.int32)
    kseg = np.concatenate([np.zeros(S1), np.ones(S2)]).astype(np.int32)
    o, m = chunk_attention(q, k, v, jnp.asarray(qpos, jnp.int32),
                           jnp.asarray(kpos), jnp.asarray(kch, jnp.int32),
                           q_seg=jnp.asarray(qseg), k_seg=jnp.asarray(kseg),
                           num_chunks=C, block_q=16, block_k=32)
    oref, mref = chunk_attention_ref(
        q, k, v, jnp.asarray(qpos, jnp.int32), jnp.asarray(kpos),
        jnp.asarray(kch, jnp.int32), q_seg=jnp.asarray(qseg),
        k_seg=jnp.asarray(kseg), num_chunks=C)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mref),
                               rtol=3e-5, atol=3e-5)
    # independent per-segment calls see exactly the same keys
    o1, m1 = chunk_attention(q[:A1], k[:S1], v[:S1],
                             jnp.asarray(qpos[:A1], jnp.int32),
                             jnp.asarray(kpos[:S1]),
                             jnp.asarray(kch[:S1], jnp.int32),
                             num_chunks=C, block_q=16, block_k=32)
    o2, m2 = chunk_attention(q[A1:], k[S1:], v[S1:],
                             jnp.asarray(qpos[A1:], jnp.int32),
                             jnp.asarray(kpos[S1:]),
                             jnp.asarray(kch[S1:], jnp.int32),
                             num_chunks=C, block_q=8, block_k=16)
    np.testing.assert_allclose(np.asarray(o[:A1]), np.asarray(o1),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(o[A1:]), np.asarray(o2),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(m[:A1]), np.asarray(m1),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(m[A1:]), np.asarray(m2),
                               rtol=3e-5, atol=3e-5)


def test_chunk_attention_mass_rows_sum_to_heads(rng):
    """Softmax mass per active row sums to H (over all chunks)."""
    A, S, H, Hkv, D, C = 24, 96, 6, 2, 32, 8
    q = _mk(rng, A, H, D)
    k = _mk(rng, S, Hkv, D)
    v = _mk(rng, S, Hkv, D)
    qpos = jnp.asarray(np.arange(A) + 8, jnp.int32)
    kpos = jnp.asarray(np.arange(S), jnp.int32)
    kch = jnp.asarray(np.arange(S) % C, jnp.int32)
    _, m = chunk_attention(q, k, v, qpos, kpos, kch, num_chunks=C,
                           block_q=8, block_k=16)
    np.testing.assert_allclose(np.asarray(m).sum(-1), H, rtol=1e-4)


@given(st.integers(1, 6), st.integers(2, 40), st.integers(1, 3),
       st.data())
def test_chunk_attention_property(a_blocks, s, g, data):
    """Random shape/position property sweep: kernel == oracle."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    Hkv = data.draw(st.sampled_from([1, 2]))
    H = Hkv * g
    D = data.draw(st.sampled_from([8, 16, 32]))
    A = a_blocks * 4
    q = _mk(rng, A, H, D)
    k = _mk(rng, s, Hkv, D)
    v = _mk(rng, s, Hkv, D)
    qpos = rng.integers(-1, s, A).astype(np.int32)
    kpos = rng.integers(-1, s, s).astype(np.int32)
    kch = rng.integers(0, 4, s).astype(np.int32)
    o, m = chunk_attention(q, k, v, jnp.asarray(qpos), jnp.asarray(kpos),
                           jnp.asarray(kch), num_chunks=4, block_q=4,
                           block_k=8)
    oref, mref = chunk_attention_ref(q, k, v, jnp.asarray(qpos),
                                     jnp.asarray(kpos), jnp.asarray(kch),
                                     num_chunks=4)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mref),
                               rtol=5e-5, atol=5e-5)


# ---------------- rope -------------------------------------------------------
@pytest.mark.parametrize("T,H,D,theta", [
    (32, 4, 32, 1e4), (50, 2, 64, 5e5), (128, 8, 128, 1e6),
])
def test_rope_vs_ref(rng, T, H, D, theta):
    x = _mk(rng, T, H, D)
    pos = jnp.asarray(rng.integers(0, 10_000, T), jnp.int32)
    for inv in (False, True):
        o = rope(x, pos, theta=theta, inverse=inv, block_t=16)
        r = rope_ref(x, pos, theta=theta, inverse=inv)
        # The kernel computes inv_freq as exp(-2 ln(theta) i / D), the
        # oracle as theta**(-i/D): fp32 ULP differences in inv_freq scale
        # by |pos| (up to 1e4 here) into ~1e-3 rad angle error (2.3e-3
        # worst value diff at theta=1e6, D=128). The identity the cache
        # store relies on (apply o remove == id, below) is exact to 2e-5
        # because both directions share the kernel.
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=4e-3, atol=4e-3)


@given(st.integers(1, 64), st.integers(0, 2**20))
def test_rope_inverse_property(t, seed):
    """apply o remove == id — the invariant the chunk-cache store relies
    on (K stored without RoPE, §4)."""
    rng = np.random.default_rng(seed)
    x = _mk(rng, t, 2, 16)
    pos = jnp.asarray(rng.integers(0, 100_000, t), jnp.int32)
    y = rope(rope(x, pos, theta=1e4, block_t=8), pos, theta=1e4,
             inverse=True, block_t=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                               rtol=2e-5, atol=2e-5)


# ---------------- decode attention ------------------------------------------
@pytest.mark.parametrize("B,S,H,Hkv,D,window", [
    (2, 64, 4, 2, 32, 0), (3, 100, 8, 2, 32, 0), (1, 48, 4, 4, 64, 16),
])
def test_decode_attention_vs_ref(rng, B, S, H, Hkv, D, window):
    q = _mk(rng, B, H, D)
    k = _mk(rng, B, S, Hkv, D)
    v = _mk(rng, B, S, Hkv, D)
    kpos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    kpos[:, -S // 4:] = -1
    qpos = jnp.asarray(rng.integers(1, S, B), jnp.int32)
    kposj = jnp.asarray(kpos)
    o = decode_attention(q, k, v, qpos, kposj, window=window, block_k=16)
    r = jnp.stack([decode_attention_ref(q[b], k[b], v[b], qpos[b],
                                        kposj[b], window=window)
                   for b in range(B)])
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("B,NB,bs,H,Hkv,D,window", [
    (2, 8, 4, 4, 2, 32, 0), (3, 12, 8, 8, 2, 32, 0), (1, 6, 4, 4, 4, 64, 8),
])
def test_paged_decode_attention_vs_ref(rng, B, NB, bs, H, Hkv, D, window):
    """The block-table-native kernel reads KV straight from the pool
    arena; it must match the numpy twin (gather-then-dense) on ragged
    block rows with -1 padding and dead (pos == -1) slots."""
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import (
        paged_decode_attention_ref,
    )
    q = _mk(rng, B, H, D)
    k_blocks = _mk(rng, NB, bs, Hkv, D)
    v_blocks = _mk(rng, NB, bs, Hkv, D)
    kpos = rng.integers(0, 64, (NB, bs)).astype(np.int32)
    kpos[rng.random((NB, bs)) < 0.2] = -1    # dead pool slots
    # ragged per-request block rows, -1 padded, possibly overlapping
    # (shared chunks reference the same physical blocks)
    NBmax = 4
    rows = np.full((B, NBmax), -1, np.int32)
    for b in range(B):
        n = int(rng.integers(1, NBmax + 1))
        rows[b, :n] = rng.choice(NB, size=n, replace=False)
    qpos = jnp.asarray(rng.integers(1, 64, B), jnp.int32)
    o = paged_decode_attention(q, k_blocks, v_blocks, jnp.asarray(kpos),
                               jnp.asarray(rows), qpos, window=window,
                               interpret=True)
    r = paged_decode_attention_ref(np.asarray(q), np.asarray(k_blocks),
                                   np.asarray(v_blocks), kpos, rows,
                                   np.asarray(qpos), window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=3e-5, atol=3e-5)


# ---------------- ssd --------------------------------------------------------
@pytest.mark.parametrize("nC,L,H,P,N", [
    (1, 8, 2, 16, 8), (3, 16, 4, 32, 16), (2, 32, 2, 64, 32),
])
def test_ssd_intra_vs_ref(rng, nC, L, H, P, N):
    xdt = _mk(rng, nC, L, H, P)
    la = jnp.asarray(-np.abs(rng.normal(size=(nC, L, H))).astype(np.float32)
                     * 0.2)
    Bm = _mk(rng, nC, L, N)
    Cm = _mk(rng, nC, L, N)
    y, stt = ssd_intra(xdt, la, Bm, Cm)
    yr, str_ = ssd_intra_ref(xdt, la, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(stt), np.asarray(str_),
                               rtol=3e-5, atol=3e-5)


def test_ssd_kernel_matches_model_layer(rng):
    """The Pallas intra-chunk kernel + JAX inter-chunk recurrence must
    reproduce the model's ssd_chunked output."""
    from repro.models.layers import ssd_chunked
    B, S, H, P, N, chunk = 2, 32, 2, 16, 8, 8
    x = _mk(rng, B, S, H, P)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, H))).astype(np.float32))
    A_log = jnp.asarray(np.zeros(H, np.float32))
    Bm = _mk(rng, B, S, N)
    Cm = _mk(rng, B, S, N)
    D = jnp.asarray(np.ones(H, np.float32))
    y_model, state_model = ssd_chunked(x, dt, A_log, Bm, Cm, D, chunk)
    # kernel path
    nC = S // chunk
    la = (dt * (-jnp.exp(A_log))).reshape(B, nC, chunk, H)
    xdt = (x * dt[..., None]).reshape(B, nC, chunk, H, P)
    Bc = Bm.reshape(B, nC, chunk, N)
    Cc = Cm.reshape(B, nC, chunk, N)
    y_in, st = ssd_intra(xdt, la, Bc, Cc)
    # inter-chunk recurrence in numpy
    y_in = np.asarray(y_in)
    st = np.asarray(st)
    cum = np.cumsum(np.asarray(la), axis=2)
    total = cum[:, :, -1]
    s = np.zeros((B, H, P, N), np.float32)
    y = np.zeros((B, nC, chunk, H, P), np.float32)
    for c in range(nC):
        y[:, c] = y_in[:, c] + np.einsum(
            "bln,blh,bhpn->blhp", np.asarray(Cc)[:, c],
            np.exp(cum[:, c]), s)
        s = s * np.exp(total[:, c])[:, :, None, None] + st[:, c]
    y = y.reshape(B, S, H, P) + np.asarray(D)[None, None, :, None] * \
        np.asarray(x)
    np.testing.assert_allclose(y, np.asarray(y_model), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, np.asarray(state_model), rtol=2e-4,
                               atol=2e-4)
