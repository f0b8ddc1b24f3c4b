"""Property-based TieredStore invariants (cache-manager tentpole).

Random interleavings of ``put``/``get``/``pin``/``unpin``/``delete``/
``prefetch`` (with and without tickets, including cancellations) and
``drain``/``flush`` must preserve:

* conservation per tier: ``used[tier]`` equals the summed sizes of the
  keys resident in that tier (SSD by the ``ssd_keys`` ledger, which
  must match the files on disk);
* exclusive residency: a key lives in at most one tier at a time;
* pinned keys are never demoted (their tier rank can only improve
  while the pin is held);
* prefetch is a no-op for deleted keys (no resurrection, no stats
  corruption);
* cancelled tickets retract their pending promotions.

Runs the store workerless: ``drain`` serves the preload queue inline,
so every interleaving is fully deterministic."""
import os
import tempfile

import numpy as np
from hypothesis import given
from hypothesis import strategies as st

from repro.core.tiers import PrefetchTicket, TieredStore, tree_nbytes

KEYS = [f"k{i}" for i in range(6)]
TIER_RANK = {"hbm": 0, "cpu": 1, "ssd": 2, None: 3}

OPS = ["put", "get", "get_nopromote", "pin", "unpin", "delete",
       "prefetch", "prefetch_ticket", "cancel", "drain", "flush"]


def _val(i, units):
    return {"k": np.full((units, 4), float(i), np.float32)}   # 16 B/unit


def _check_invariants(ts, alive):
    # exclusive residency
    hbm, cpu, ssd = set(ts.hbm), set(ts.cpu), set(ts.ssd_keys)
    assert not (hbm & cpu) and not (hbm & ssd) and not (cpu & ssd)
    # conservation per tier
    assert ts.used["hbm"] == sum(ts.sizes[k] for k in hbm)
    assert ts.used["cpu"] == sum(ts.sizes[k] for k in cpu)
    assert ts.used["ssd"] == sum(ts.ssd_keys.values())
    # the SSD ledger matches the files on disk
    on_disk = {f[:-4] for f in os.listdir(ts.ssd_dir)
               if f.endswith(".npz")}
    assert ssd == on_disk
    # no dead key occupies a tier
    for k in hbm | cpu | ssd:
        assert k in alive
    # a deleted key is gone from everywhere
    for k in set(KEYS) - set(alive):
        assert ts.where(k) is None


@given(st.lists(st.tuples(st.sampled_from(OPS), st.integers(0, 5),
                          st.integers(1, 6)),
                max_size=50))
def test_random_interleavings_preserve_tier_invariants(ops):
    ts = TieredStore(8 * 16, 8 * 16, tempfile.mkdtemp(prefix="cc-prop-"),
                     start_worker=False)
    alive = {}                 # key -> value (the expected bytes)
    pinned_rank = {}           # key -> best (lowest) rank since pin
    tickets = []
    for op, a, units in ops:
        key = KEYS[a % len(KEYS)]
        if op == "put":
            val = _val(a, units)
            alive[key] = val
            ts.put(key, val)
        elif op in ("get", "get_nopromote"):
            val, info = ts.get(key, promote=op == "get")
            if key in alive:
                np.testing.assert_array_equal(val["k"], alive[key]["k"])
            else:
                assert val is None and info is None
        elif op == "pin":
            ts.pin(key)
            pinned_rank.setdefault(key, TIER_RANK[ts.where(key)])
        elif op == "unpin":
            ts.unpin(key)
            if key not in ts.pins:
                pinned_rank.pop(key, None)
        elif op == "delete":
            ts.delete(key)
            alive.pop(key, None)
            pinned_rank.pop(key, None)
        elif op == "prefetch":
            ts.prefetch(key)
        elif op == "prefetch_ticket":
            t = PrefetchTicket()
            tickets.append(t)
            ts.prefetch(key, ticket=t)
        elif op == "cancel" and tickets:
            tickets[a % len(tickets)].cancel()
        elif op == "drain":
            ts.drain()
        elif op == "flush":
            ts.flush()
        # pinned keys never demoted: rank can only improve (promotion)
        for k, best in list(pinned_rank.items()):
            now = TIER_RANK[ts.where(k)]
            if k in alive:
                assert now <= best, f"pinned {k} demoted {best}->{now}"
                pinned_rank[k] = min(best, now)
        _check_invariants(ts, alive)

    # settle everything and re-check; deleted keys must stay gone even
    # if promotions for them are still queued (prefetch no-op)
    ts.drain()
    _check_invariants(ts, alive)
    for t in tickets:
        t.cancel()
    ts.drain()
    _check_invariants(ts, alive)


@given(st.lists(st.integers(0, 5), min_size=1, max_size=12))
def test_prefetch_never_resurrects_deleted_keys(ids):
    ts = TieredStore(4 * 16, 4 * 16, tempfile.mkdtemp(prefix="cc-res-"),
                     start_worker=False)
    for i in ids:
        key = KEYS[i % len(KEYS)]
        ts.put(key, _val(i, 2))
        ts.prefetch(key)
        ts.delete(key)
    ts.drain()
    for key in KEYS:
        assert ts.where(key) is None
    assert ts.used == {"hbm": 0, "cpu": 0, "ssd": 0}


# ---- hit/promote vs delete/put interleavings (quantized-tiers PR) ----------
# ``get``'s slow path drops the lock during the (possibly delayed) load
# and dequantize. Historically it then read ``self.sizes[key]`` outside
# the lock — a concurrent ``delete`` raised KeyError on the lane worker
# — and ``_promote`` happily installed the stale value over whatever a
# concurrent ``put`` had just written. Now the size and a per-key
# generation token are snapshotted under the lock at the hit, and
# ``_promote`` drops values whose generation moved.

def _cpu_resident(ts, key, val):
    """Place ``key`` on the cpu tier of a store whose HBM fits it."""
    ts.put(key, val)
    ts._demote(key, "hbm")
    assert ts.where(key) == "cpu"


def test_delete_during_slow_get_neither_crashes_nor_resurrects():
    import threading
    ts = TieredStore(1 << 20, 1 << 20,
                     tempfile.mkdtemp(prefix="cc-race-del-"),
                     start_worker=False)
    _cpu_resident(ts, "x", _val(1, 8))
    ts.load_delay_s = 0.08
    got = {}

    def reader():
        got["ret"] = ts.get("x")     # cpu hit; sleeps mid-flight

    t = threading.Thread(target=reader)
    t.start()
    import time
    time.sleep(0.02)
    ts.delete("x")                   # interleaves with the in-flight get
    t.join(timeout=5.0)
    assert not t.is_alive()
    val, info = got["ret"]
    # the read raced the delete: whichever snapshot it took, it must not
    # crash, and the delete must win durably (no stale resurrection)
    if val is not None:
        np.testing.assert_array_equal(val["k"], _val(1, 8)["k"])
        assert info.tier == "cpu"
    assert ts.where("x") is None
    assert ts.used == {"hbm": 0, "cpu": 0, "ssd": 0}
    _check_invariants(ts, {})


def test_put_during_slow_get_is_not_clobbered_by_stale_promote():
    import threading
    ts = TieredStore(1 << 20, 1 << 20,
                     tempfile.mkdtemp(prefix="cc-race-put-"),
                     start_worker=False)
    old, new = _val(1, 8), _val(2, 4)
    _cpu_resident(ts, "x", old)
    ts.load_delay_s = 0.08
    got = {}

    def reader():
        got["ret"] = ts.get("x")

    t = threading.Thread(target=reader)
    t.start()
    import time
    time.sleep(0.02)
    ts.put("x", new)                 # overwrite while the get sleeps
    t.join(timeout=5.0)
    assert not t.is_alive()
    val, _info = got["ret"]
    np.testing.assert_array_equal(val["k"], old["k"])   # snapshot read
    # the stale promote must have been dropped: the store serves the
    # NEW value with the NEW size accounting
    cur, _ = ts.get("x", promote=False)
    np.testing.assert_array_equal(cur["k"], new["k"])
    assert ts.sizes["x"] == tree_nbytes(new)
    _check_invariants(ts, {"x": new})


# ---- quantized round-trip property (quantized-tiers PR) --------------------

@given(st.lists(st.tuples(st.integers(0, 5), st.integers(16, 24)),
                min_size=1, max_size=10),
       st.sampled_from(["int8", "fp8"]))
def test_quant_round_trip_preserves_ledger_and_values(puts, scheme):
    """put(fp32) -> demote -> demote -> promote -> get: conservation
    per tier, SSD ledger == real disk payload bytes, and dequantized KV
    within the scheme's error bound."""
    from repro.core.tiers import quant_error_bound, stored_nbytes
    ts = TieredStore(1 << 20, 1 << 20,
                     tempfile.mkdtemp(prefix=f"cc-qprop-{scheme}-"),
                     start_worker=False,
                     tier_dtypes={"cpu": scheme, "ssd": scheme})
    alive = {}
    for i, units in puts:
        key = KEYS[i % len(KEYS)]
        # big float leaves (>= 64 elems) so the codec actually engages
        val = {"k": np.linspace(-1.0, 1.0, units * 16, dtype=np.float32)
               .reshape(units, 16) * (i + 1)}
        alive[key] = val
        ts.put(key, val)
    _check_invariants(ts, alive)
    ts.flush()                       # hbm -> cpu -> ssd: everything deep
    _check_invariants(ts, alive)
    for key, val in alive.items():
        assert ts.where(key) == "ssd"
        # quantized sizes ledger == the bytes actually on disk
        with np.load(ts._ssd_path(key)) as z:
            payload = sum(z[f].nbytes for f in z.files
                          if not f.startswith("__"))
        assert ts.sizes[key] == payload == ts.ssd_keys[key]
    for key, val in alive.items():
        out, info = ts.get(key)      # promotes back to HBM
        err = float(np.abs(out["k"] - val["k"]).max())
        assert err <= quant_error_bound(val["k"], scheme), (key, err)
        assert info.nbytes < tree_nbytes(val)   # stored bytes moved
    _check_invariants(ts, alive)
    for key in alive:
        assert ts.where(key) == "hbm"
        # HBM holds raw fp32 again: the ledger re-inflated on promote
        assert ts.sizes[key] == tree_nbytes(alive[key])
