"""Property-based KV-pool invariants (reservation protocol + CoW +
zero-copy shared segments).

Random interleavings of ``reserve``/``commit``/``cancel``/``alloc``/
``share``/``release``/``write_prefill``/``append_token`` plus the
shared-segment ops (``pin`` a canonical run, ``share_ref`` it into a
table, ``cow`` a row write over shared blocks, ``unpin``) and the
preemption teardown (``preempt``: ``reclaim_request`` — release a
table that may reference shared runs AND cancel its possibly
partially-drawn reservation in one compound op) must preserve:

* refcounts >= 0 everywhere;
* no block is simultaneously free and live (or free and reserved);
* conservation: ``free_blocks + live_blocks + reserved_blocks ==
  num_blocks`` (shared blocks count once no matter how many tables and
  canonical runs reference them);
* ``gather`` round-trips every written token's KV bit-exactly;
* a CoW write never mutates a canonical run's bytes or another
  reader's gathered KV.
"""
import numpy as np
from hypothesis import given
from hypothesis import strategies as st

from repro.serving.kvpool import BlockTable, KVPool

L, HKV, DH, BS, NB = 2, 2, 4, 4, 12

OPS = ["alloc", "release", "share", "reserve", "commit", "cancel",
       "write", "append", "free_table", "pin", "share_ref", "cow",
       "unpin", "preempt"]


def _pool():
    return KVPool(num_layers=L, kv_heads=HKV, head_dim=DH,
                  num_blocks=NB, block_size=BS)


def _tok(i):
    """Deterministic, distinct per-token KV payload (bit-exact in f32)."""
    base = np.arange(L * HKV * DH, dtype=np.float32).reshape(L, HKV, DH)
    return base + 1000.0 * i


def _check_invariants(pool, reservations, tables, runs=()):
    assert (pool.refs >= 0).all()
    free = pool.free
    free_set = set(free)
    assert len(free_set) == len(free), "duplicate block in free list"
    live = {b for b in range(pool.num_blocks) if pool.refs[b] > 0}
    assert not (free_set & live), "block both free and live"
    reserved = [b for r in reservations if not r.closed for b in r.blocks]
    assert len(set(reserved)) == len(reserved)
    assert not (set(reserved) & free_set), "block both free and reserved"
    assert not (set(reserved) & live), "block both live and reserved"
    assert pool.reserved_blocks == len(reserved)
    assert all(pool.refs[b] == 0 for b in reserved)
    assert pool.free_blocks + pool.live_blocks + pool.reserved_blocks \
        == pool.num_blocks
    assert pool.free_tokens == pool.free_blocks * pool.block_size
    # every table's written KV reads back bit-exactly
    for table, _res, exp_k, exp_v, exp_pos in tables:
        pad = max(pool.block_size,
                  pool.blocks_needed(max(table.length, 1))
                  * pool.block_size)
        gk, gv, gpos = pool.gather(table, pad)
        n = table.length
        assert n == len(exp_k)
        if n:
            np.testing.assert_array_equal(
                gk[:, :n], np.stack(exp_k, axis=1))
            np.testing.assert_array_equal(
                gv[:, :n], np.stack(exp_v, axis=1))
            np.testing.assert_array_equal(gpos[:n], np.asarray(exp_pos))
        assert (gpos[n:] == -1).all()
    # canonical shared runs keep their bytes no matter what readers do
    # (CoW must clone before any write lands on a shared block)
    for run in runs:
        assert all(pool.refs[b] >= 1 for b in run["blocks"])
        for i, b in enumerate(run["blocks"]):
            s0 = i * pool.block_size
            s1 = s0 + pool.block_size
            np.testing.assert_array_equal(
                pool.k[:, b], np.stack(run["exp_k"][s0:s1], axis=1))
            np.testing.assert_array_equal(
                pool.v[:, b], np.stack(run["exp_v"][s0:s1], axis=1))
            np.testing.assert_array_equal(
                pool.pos[b], np.asarray(run["exp_pos"][s0:s1]))


def _pin_run(pool, counter, S):
    """Materialize a canonical shared run of S tokens; returns (run
    dict with expected content incl. the zeroed tail padding, tokens
    consumed) or (None, 0)."""
    blocks = pool.alloc(pool.blocks_needed(S))
    if blocks is None:
        return None, 0
    toks = [_tok(counter + i) for i in range(S)]
    k = np.stack(toks, axis=1)
    pos = np.arange(S, dtype=np.int32)
    pool.write_run(blocks, k, k + 0.5, pos)
    pad = len(blocks) * pool.block_size - S
    zero = np.zeros((L, HKV, DH), np.float32)
    return {
        "blocks": blocks,
        "exp_k": toks + [zero] * pad,
        "exp_v": [t + 0.5 for t in toks] + [zero] * pad,
        "exp_pos": list(pos) + [-1] * pad,
    }, S


@given(st.lists(st.tuples(st.sampled_from(OPS), st.integers(0, 5)),
                max_size=60))
def test_random_interleavings_preserve_invariants(ops):
    pool = _pool()
    held = []           # block lists we own one reference to
    reservations = []   # every Reservation ever made (closed ones too)
    tables = []         # (table, reservation|None, exp_k, exp_v, exp_pos)
    runs = []           # canonical shared runs (we hold the owner ref)
    counter = 0
    for step, (op, n) in enumerate(ops):
        open_res = [r for r in reservations if not r.closed]
        if op == "alloc":
            got = pool.alloc(n % 4 + 1)
            if got is not None:
                held.append(got)
        elif op == "release" and held:
            pool.release(held.pop(n % len(held)))
        elif op == "share" and held:
            blocks = held[n % len(held)]
            pool.share(blocks)
            held.append(list(blocks))
        elif op == "reserve":
            res = pool.reserve(n % 5 + 1)
            if res is not None:
                reservations.append(res)
        elif op == "commit" and open_res:
            pool.commit(open_res[n % len(open_res)])
        elif op == "cancel" and open_res:
            pool.cancel(open_res[n % len(open_res)])
        elif op == "write":
            S = n % 7 + 1
            res = open_res[n % len(open_res)] if open_res and n % 2 \
                else None
            toks = [_tok(counter + i) for i in range(S)]
            counter += S
            k = np.stack(toks, axis=1)
            v = k + 0.5
            pos = np.arange(S, dtype=np.int32)
            table = BlockTable()
            if pool.write_prefill(table, k, v, pos, reservation=res):
                tables.append((table, res,
                               toks, [t + 0.5 for t in toks], list(pos)))
        elif op == "append" and tables:
            table, res, exp_k, exp_v, exp_pos = tables[n % len(tables)]
            tok = _tok(counter)
            counter += 1
            pos = exp_pos[-1] + 1 if exp_pos else 0
            if pool.append_token(table, tok, tok + 0.5, pos,
                                 reservation=res):
                exp_k.append(tok)
                exp_v.append(tok + 0.5)
                exp_pos.append(pos)
        elif op == "free_table" and tables:
            table, _res, _k, _v, _pos = tables.pop(n % len(tables))
            pool.free_table(table)
        elif op == "pin":
            run, used = _pin_run(pool, counter, n % 7 + 1)
            counter += used
            if run is not None:
                runs.append(run)
        elif op == "share_ref" and runs:
            # zero-copy: a new table references the canonical run's
            # blocks (padding included — it is part of the used span)
            run = runs[n % len(runs)]
            table = BlockTable()
            pool.append_shared(table, run["blocks"])
            tables.append((table, None, list(run["exp_k"]),
                           list(run["exp_v"]), list(run["exp_pos"])))
        elif op == "cow" and tables:
            # overwrite one slot in place; shared blocks must clone
            # first (the canonical-run check below catches any leak)
            table, res, exp_k, exp_v, exp_pos = tables[n % len(tables)]
            if table.length:
                slot = n % table.length
                tok = _tok(counter)
                counter += 1
                pos = max(exp_pos) + 1 if exp_pos else 0
                if pool.write_rows(table, np.asarray([slot]),
                                   tok[:, None], tok[:, None] + 0.5,
                                   np.asarray([pos], np.int32),
                                   reservation=res):
                    exp_k[slot] = tok
                    exp_v[slot] = tok + 0.5
                    exp_pos[slot] = pos
        elif op == "unpin" and runs:
            run = runs.pop(n % len(runs))
            pool.release(run["blocks"])      # drop the owner reference
        elif op == "preempt" and tables:
            # preemption/expiry teardown: drop a table (its blocks may
            # reference canonical runs mid-share) and cancel its
            # reservation — possibly partially drawn, possibly shared
            # with other tables (they fall back to the free list) — in
            # one compound op; cancel-with-shared-refs-in-flight must
            # keep free + live + reserved == num_blocks
            table, res, _k, _v, _pos = tables.pop(n % len(tables))
            freed = pool.reclaim_request(table, res)
            assert freed >= 0
            assert table.blocks == [] and table.length == 0
            assert res is None or res.closed
        _check_invariants(pool, reservations, tables, runs)

    # drain everything: the pool must return to fully free
    for table, _res, _k, _v, _pos in tables:
        pool.free_table(table)
    for blocks in held:
        pool.release(blocks)
    for run in runs:
        pool.release(run["blocks"])
    for res in reservations:
        pool.cancel(res)
    assert pool.free_blocks == pool.num_blocks
    assert pool.live_blocks == 0 and pool.reserved_blocks == 0


def _deref(pool, row):
    """Dereference a pool-flat slot-index row through ``block_view`` —
    the exact read the paged attention path performs on device."""
    kf, vf, pf = pool.block_view()
    kflat = kf.reshape(kf.shape[0], -1, *kf.shape[3:])
    vflat = vf.reshape(vf.shape[0], -1, *vf.shape[3:])
    pflat = pf.reshape(-1)
    safe = np.maximum(row, 0)
    valid = row >= 0
    k = np.where(valid[None, :, None, None], kflat[:, safe], 0.0)
    v = np.where(valid[None, :, None, None], vflat[:, safe], 0.0)
    pos = np.where(valid, pflat[safe], -1).astype(np.int32)
    return k, v, pos


PAGED_OPS = ["write", "append", "cow", "share_ref", "pin", "free_table",
             "unpin", "clear_dirty"]


@given(st.lists(st.tuples(st.sampled_from(PAGED_OPS), st.integers(0, 5)),
                max_size=50))
def test_paged_ops_block_view_and_cow_swap(ops):
    """Paged-mode pool contract under random op sequences:

    * ``block_view`` is zero-copy — the returned arrays ARE the arenas,
      so every host write is immediately visible through a view taken
      at any earlier time;
    * ``table_slot_index`` dereferenced through the view reproduces
      ``gather(compact=True)`` bit-for-bit (the bit-identity seam);
    * the CoW swap invariant: a write over a shared block swaps the
      WRITER's index entry to a clone — a slot-index row exported by
      another reader before the write still dereferences to the exact
      pre-write bytes;
    * ``ensure_append_slot`` pre-opens exactly the slot the next
      ``append_token`` fills, without advancing ``table.length``, and
      marks every mutated block dirty for the device twin.
    """
    pool = _pool()
    kv0, vv0, pv0 = pool.block_view()      # early view: must stay live
    tables = []         # (table, exp_k, exp_v, exp_pos)
    runs = []
    counter = 0
    for op, n in ops:
        if op == "write":
            S = n % 7 + 1
            toks = [_tok(counter + i) for i in range(S)]
            counter += S
            k = np.stack(toks, axis=1)
            table = BlockTable()
            if pool.write_prefill(table, k, k + 0.5,
                                  np.arange(S, dtype=np.int32)):
                tables.append((table, None, toks,
                               [t + 0.5 for t in toks],
                               list(range(S))))
        elif op == "append" and tables:
            table, _r, exp_k, exp_v, exp_pos = tables[n % len(tables)]
            length_before = table.length
            slot = pool.ensure_append_slot(table)
            assert table.length == length_before, \
                "ensure_append_slot must not advance length"
            if slot is not None:
                b, off = divmod(slot, pool.block_size)
                assert table.blocks[length_before // pool.block_size] == b
                assert off == length_before % pool.block_size
                assert pool.refs[b] == 1, "pre-opened block must be private"
                tok = _tok(counter)
                counter += 1
                pos = exp_pos[-1] + 1 if exp_pos else 0
                assert pool.append_token(table, tok, tok + 0.5, pos), \
                    "append after ensure_append_slot cannot fail"
                # the token landed in the pre-opened slot, visible
                # through the EARLY view (zero-copy aliasing)
                np.testing.assert_array_equal(
                    kv0[:, b, off], tok)
                np.testing.assert_array_equal(
                    vv0[:, b, off], tok + 0.5)
                assert pv0[b, off] == pos
                exp_k.append(tok)
                exp_v.append(tok + 0.5)
                exp_pos.append(pos)
        elif op == "cow" and tables:
            table, _r, exp_k, exp_v, exp_pos = tables[n % len(tables)]
            if not table.length:
                continue
            # another reader exports its rows BEFORE the write; the
            # CoW swap invariant says those rows still dereference to
            # the same bytes afterwards
            snapshots = []
            for other, _r2, ok, ov, opos in tables:
                if other is table:
                    continue
                pad = max(len(ok), 1)
                row = pool.table_slot_index(other, pad)
                snapshots.append((row, _deref(pool, row)))
            slot = n % table.length
            tok = _tok(counter)
            counter += 1
            pos = max(exp_pos) + 1 if exp_pos else 0
            if pool.write_rows(table, np.asarray([slot]),
                               tok[:, None], tok[:, None] + 0.5,
                               np.asarray([pos], np.int32)):
                exp_k[slot] = tok
                exp_v[slot] = tok + 0.5
                exp_pos[slot] = pos
                for row, (sk, sv, spos) in snapshots:
                    nk, nv, npos_ = _deref(pool, row)
                    np.testing.assert_array_equal(nk, sk)
                    np.testing.assert_array_equal(nv, sv)
                    np.testing.assert_array_equal(npos_, spos)
        elif op == "share_ref" and runs:
            run = runs[n % len(runs)]
            table = BlockTable()
            pool.append_shared(table, run["blocks"])
            tables.append((table, None, list(run["exp_k"]),
                           list(run["exp_v"]), list(run["exp_pos"])))
        elif op == "pin":
            run, used = _pin_run(pool, counter, n % 7 + 1)
            counter += used
            if run is not None:
                runs.append(run)
        elif op == "free_table" and tables:
            table, _r, _k, _v, _pos = tables.pop(n % len(tables))
            pool.free_table(table)
        elif op == "unpin" and runs:
            run = runs.pop(n % len(runs))
            pool.release(run["blocks"])
        elif op == "clear_dirty":
            pool.clear_dirty(pool.dirty_blocks())
            assert pool.dirty_blocks() == []
        # the view is the arena: identity, not a copy
        kv, vv, pv = pool.block_view()
        assert kv is kv0 and vv is vv0 and pv is pv0
        # slot-index deref == gather(compact=True), element for element
        for table, _r, exp_k, _exp_v, _exp_pos in tables:
            pad = max(len(exp_k), 1)
            row = pool.table_slot_index(table, pad)
            dk, dv, dpos = _deref(pool, row)
            gk, gv, gpos = pool.gather(table, pad, compact=True)
            np.testing.assert_array_equal(dk, gk)
            np.testing.assert_array_equal(dv, gv)
            np.testing.assert_array_equal(dpos, gpos)
        _check_invariants(pool, [], tables, runs)

    for table, _r, _k, _v, _pos in tables:
        pool.free_table(table)
    for run in runs:
        pool.release(run["blocks"])
    assert pool.free_blocks == pool.num_blocks


@given(st.lists(st.integers(0, 4), min_size=0, max_size=8))
def test_cow_append_preserves_shared_content(ns):
    """Appending into a block shared with another table must CoW: the
    sharer's view stays bit-identical, the appender's view gains the
    token, and accounting still conserves."""
    pool = _pool()
    S = 3
    toks = [_tok(i) for i in range(S)]
    k = np.stack(toks, axis=1)
    table = BlockTable()
    assert pool.write_prefill(table, k, k, np.arange(S, dtype=np.int32))
    shared = list(table.blocks)
    pool.share(shared)
    before = pool.k[:, shared[0]].copy()
    res = pool.reserve(2)
    pos = S
    for i, _ in enumerate(ns):
        tok = _tok(100 + i)
        if not pool.append_token(table, tok, tok, pos, reservation=res):
            break
        toks.append(tok)
        pos += 1
        np.testing.assert_array_equal(pool.k[:, shared[0]], before)
        gk, _gv, gpos = pool.gather(table, 16)
        np.testing.assert_array_equal(gk[:, :len(toks)],
                                      np.stack(toks, axis=1))
        assert pool.free_blocks + pool.live_blocks \
            + pool.reserved_blocks == pool.num_blocks
    pool.cancel(res)
    pool.release(shared)
    pool.free_table(table)
    assert pool.free_blocks == pool.num_blocks
