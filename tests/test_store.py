"""Chunk store (NxM variants, f_r eviction) + tiered storage tests."""
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.core.chunkstore import ChunkStore, chunk_hash
from repro.core.scoring import ChunkScores
from repro.core.tiers import TieredStore, tree_nbytes


def _scores(prefix, cci=0.6, n=8):
    return ChunkScores(chunk_index=len(prefix), length=n, a_bar=0.1,
                       b_bar=0.2, cci=cci, prefix_hashes=list(prefix),
                       prefix_inter=[1.0] * len(prefix),
                       token_inter=np.arange(n, dtype=np.float64))


def _kv(n=8, L=2):
    return {"k": np.zeros((L, n, 2, 4), np.float32),
            "v": np.zeros((L, n, 2, 4), np.float32)}


@pytest.fixture
def store(tmp_path):
    tiers = TieredStore(1 << 22, 1 << 22, str(tmp_path / "ssd"),
                        start_worker=False)
    return ChunkStore(tiers, n_chunks=3, m_variants=2)


def test_capacity_and_fr_eviction(store):
    # fill to capacity 3*2=6
    vars_ = []
    for i in range(6):
        v = store.add_variant(f"c{i % 3}", _kv(), _scores([]))
        vars_.append(v)
    assert store.num_variants() == 6
    # use some variants so they gain f_r
    for v in vars_[:5]:
        store.record_use(v, cfo_value=0.5)
    # adding a 7th evicts the only unused (lowest f_r) variant
    store.add_variant("c9", _kv(), _scores([]))
    assert store.num_variants() == 6
    assert vars_[5].variant_id not in [
        v.variant_id for vs in store.table.values() for v in vs]
    assert store.evictions == 1


def test_best_variant_minimizes_cfo(store):
    h = "cc"
    v1 = store.add_variant(h, _kv(), _scores(["a", "b"]))      # old prefix ab
    v2 = store.add_variant(h, _kv(), _scores(["x"]))           # old prefix x
    best, cfo = store.best_variant(h, ["a", "b"])
    assert best is v1                # exact prefix match -> beta'=1 -> cfo 0
    assert cfo == pytest.approx(0.0)
    best2, cfo2 = store.best_variant(h, ["x"])
    assert best2 is v2


def test_fr_accumulates_inverse_cfo(store):
    v = store.add_variant("c", _kv(), _scores([]))
    store.record_use(v, 0.25)
    store.record_use(v, 0.5)
    assert v.f_r == pytest.approx(4.0 + 2.0)
    assert v.uses == 2


def test_get_kv_roundtrip(store):
    kv = _kv()
    kv["k"] += 3.0
    v = store.add_variant("c", kv, _scores([]))
    got, info = store.get_kv(v)
    np.testing.assert_array_equal(got["k"], kv["k"])
    assert info.tier in ("hbm", "cpu", "ssd")


@given(st.lists(st.integers(0, 9), min_size=1, max_size=40))
def test_store_capacity_invariant(hash_ids):
    """Under any insertion sequence the store never exceeds N*M."""
    import tempfile
    tiers = TieredStore(1 << 22, 1 << 22, tempfile.mkdtemp(),
                        start_worker=False)
    store = ChunkStore(tiers, n_chunks=2, m_variants=3)
    for i, h in enumerate(hash_ids):
        v = store.add_variant(f"h{h}", _kv(), _scores([]))
        if i % 3 == 0:
            store.record_use(v, 0.5)
        assert store.num_variants() <= store.capacity


# ---- tiers -------------------------------------------------------------------
def test_tier_demotion_and_ssd_roundtrip(tmp_path):
    small = TieredStore(hbm_bytes=3000, cpu_bytes=3000,
                        ssd_dir=str(tmp_path / "ssd"), start_worker=False)
    trees = {}
    for i in range(5):
        t = {"k": np.full((10, 16), float(i), np.float32)}  # 640 B
        trees[f"x{i}"] = t
        small.put(f"x{i}", t)
    # everything still retrievable, value-correct, from some tier
    for i in range(5):
        val, info = small.get(f"x{i}", promote=False)
        np.testing.assert_array_equal(val["k"], trees[f"x{i}"]["k"])
    assert small.stats["demotions"] >= 0
    # force overflow to SSD
    big = {"k": np.zeros((100, 16), np.float32)}            # 6.4 KB > caps
    tier = small.put("big", big)
    assert tier == "ssd"
    val, info = small.get("big", promote=False)
    assert info.tier == "ssd"
    assert info.seconds_measured > 0
    np.testing.assert_array_equal(val["k"], big["k"])


def test_tier_prefetch_promotes(tmp_path):
    ts = TieredStore(hbm_bytes=1 << 20, cpu_bytes=1 << 20,
                     ssd_dir=str(tmp_path / "ssd"))
    t = {"k": np.ones((4, 4), np.float32)}
    ts.put("a", t)
    # demote manually to cpu then prefetch back
    with ts.lock:
        if "a" in ts.hbm:
            ts._demote("a", "hbm")
    assert ts.where("a") in ("cpu", "ssd")
    ts.prefetch("a")
    ts.drain()
    import time
    for _ in range(100):
        if ts.where("a") == "hbm":
            break
        time.sleep(0.01)
    assert ts.where("a") == "hbm"
    ts.close()


def test_tree_nbytes():
    t = {"a": np.zeros((4, 4), np.float32),
         "b": [np.zeros(8, np.int32)]}
    assert tree_nbytes(t) == 4 * 4 * 4 + 8 * 4


def test_int8_kv_quantization(tmp_path):
    """Beyond-paper: int8 chunk-caches — 4x smaller, bounded error."""
    import tempfile
    rng = np.random.default_rng(0)
    tiers = TieredStore(1 << 22, 1 << 22, str(tmp_path / "q"),
                        start_worker=False)
    store = ChunkStore(tiers, 4, 2, quantize_kv=True)
    kv = {"k": rng.normal(size=(2, 8, 2, 4)).astype(np.float32),
          "v": rng.normal(size=(2, 8, 2, 4)).astype(np.float32)}
    v = store.add_variant("c", {k: x.copy() for k, x in kv.items()},
                          _scores([]))
    got, _ = store.get_kv(v)
    for name in ("k", "v"):
        err = np.abs(got[name] - kv[name]).max()
        scale = np.abs(kv[name]).max() / 127.0
        assert err <= scale * 1.01
    # smaller than fp32 even at this tiny shape (scales are per-token and
    # amortize to ~nothing at production H*D; here they are 1/3 of bytes)
    assert v.nbytes < kv["k"].nbytes * 2 * 0.5
