"""Paged KV pool invariants (hypothesis state-machine style)."""
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.serving.kvpool import BlockTable, KVPool


def _pool(blocks=16):
    return KVPool(num_layers=2, kv_heads=2, head_dim=4, num_blocks=blocks,
                  block_size=4)


def test_alloc_free_refcount():
    p = _pool(8)
    a = p.alloc(3)
    assert len(a) == 3 and p.free_blocks == 5
    p.share(a)
    p.release(a)                      # refcount 2 -> 1, still held
    assert p.free_blocks == 5
    p.release(a)
    assert p.free_blocks == 8
    assert p.alloc(9) is None         # over-capacity alloc fails cleanly


def test_write_gather_roundtrip(rng):
    p = _pool(8)
    t = BlockTable()
    S = 10
    k = rng.normal(size=(2, S, 2, 4)).astype(np.float32)
    v = rng.normal(size=(2, S, 2, 4)).astype(np.float32)
    pos = np.arange(S, dtype=np.int32)
    assert p.write_prefill(t, k, v, pos)
    gk, gv, gpos = p.gather(t, pad_to=16)
    np.testing.assert_array_equal(gk[:, :S], k)
    np.testing.assert_array_equal(gv[:, :S], v)
    np.testing.assert_array_equal(gpos[:S], pos)
    assert (gpos[S:] == -1).all()


def test_append_token_and_cow(rng):
    p = _pool(8)
    t = BlockTable()
    k = rng.normal(size=(2, 3, 2, 4)).astype(np.float32)
    p.write_prefill(t, k, k, np.arange(3, dtype=np.int32))
    shared = list(t.blocks)
    p.share(shared)                   # another request shares the block
    before = p.k[:, shared[0]].copy()
    ktok = np.ones((2, 2, 4), np.float32)
    assert p.append_token(t, ktok, ktok, pos=3)   # lands inside the block
    # copy-on-write: table moved to a fresh block; shared one untouched
    assert t.blocks[0] != shared[0]
    assert p.refs[shared[0]] == 1
    np.testing.assert_array_equal(p.k[:, shared[0]], before)
    gk, _, gpos = p.gather(t, pad_to=8)
    np.testing.assert_array_equal(gk[:, 3], ktok)
    assert gpos[3] == 3


@given(st.lists(st.tuples(st.sampled_from(["alloc", "free"]),
                          st.integers(1, 5)), max_size=30))
def test_pool_accounting_invariant(ops):
    p = _pool(12)
    held = []
    for op, n in ops:
        if op == "alloc":
            got = p.alloc(n)
            if got is not None:
                held.append(got)
        elif held:
            p.release(held.pop())
        used = sum(len(h) for h in held)
        assert p.free_blocks == 12 - used
        assert all(p.refs[b] == 1 for h in held for b in h)


def test_gather_empty_table_is_all_padding():
    """length == 0 / no blocks: a well-formed all-padding view, not an
    inconsistent zero-row slice of an empty block list."""
    p = _pool(8)
    t = BlockTable()
    k, v, pos = p.gather(t, pad_to=8)
    assert k.shape == (2, 8, 2, 4) and v.shape == k.shape
    assert pos.shape == (8,)
    assert (pos == -1).all()
    assert (k == 0).all() and (v == 0).all()


def test_reserve_commit_cancel_accounting():
    p = _pool(8)
    res = p.reserve(3)
    assert res is not None and res.remaining == 3
    # reserved blocks are excluded from free headroom
    assert p.free_blocks == 5 and p.reserved_blocks == 3
    assert p.free_tokens == 5 * 4
    assert p.reserve(6) is None           # over-reservation fails cleanly
    # write draws from the reservation, not the free list
    t = BlockTable()
    k = np.arange(2 * 6 * 2 * 4, dtype=np.float32).reshape(2, 6, 2, 4)
    assert p.write_prefill(t, k, k, np.arange(6, dtype=np.int32),
                           reservation=res)
    assert p.free_blocks == 5 and p.reserved_blocks == 1
    assert p.live_blocks == 2 and res.drawn == 2
    p.commit(res)                         # undrawn remainder returns free
    assert res.closed
    assert p.free_blocks == 6 and p.reserved_blocks == 0
    p.commit(res)                         # double-close is a no-op
    assert p.free_blocks == 6
    res2 = p.reserve(2)
    p.cancel(res2)
    assert p.free_blocks == 6 and p.reserved_blocks == 0
    p.free_table(t)
    assert p.free_blocks == 8


def test_append_token_draws_from_reservation(rng):
    p = _pool(8)
    res = p.reserve(2)
    t = BlockTable()
    k = rng.normal(size=(2, 4, 2, 4)).astype(np.float32)
    assert p.write_prefill(t, k, k, np.arange(4, dtype=np.int32),
                           reservation=res)
    assert res.remaining == 1
    free_before = p.free_blocks
    ktok = np.ones((2, 2, 4), np.float32)
    assert p.append_token(t, ktok, ktok, pos=4, reservation=res)
    # the new block came from the reservation, not the free list
    assert p.free_blocks == free_before and res.remaining == 0
    p.commit(res)
    p.free_table(t)
    assert p.free_blocks == 8


def test_free_table_releases_everything(rng):
    p = _pool(8)
    t = BlockTable()
    k = rng.normal(size=(2, 20, 2, 4)).astype(np.float32)
    p.write_prefill(t, k, k, np.arange(20, dtype=np.int32))
    assert p.free_blocks == 3
    p.free_table(t)
    assert p.free_blocks == 8
    assert t.length == 0
