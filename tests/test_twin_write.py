"""Prefill KV written into the paged-decode pool twin on the device.

A paged engine keeps a device twin of the pool's block arenas. Blocks a
prefill fills from its own result (private segments in zero-copy mode,
the dense ``write_prefill`` in copy mode) reach the twin through one
jitted gather from the pass's packed device KV; everything else the
host alone writes (shared-run loads, CoW clones, recompute fix-up rows,
decode-opened append blocks) is uploaded from the dirty log. These
tests hold the twin to the host pool bit for bit — K, V and positions
in every slot of every block a live table holds — after every prefill,
across the write modes, packing, block reuse and shared-chunk hits;
and count what each path moved.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_tiny
from repro.core.prefill import pack_paged_cache
from repro.models import model as M
from repro.serving.api import EngineSpec, build_engine
from repro.serving.rag import KnowledgeBase
from repro.serving.request import Request, State
from repro.serving.scheduler import SchedulerConfig
from repro.serving.workload import WorkloadConfig, generate


@pytest.fixture(scope="module")
def world():
    cfg = get_tiny("llama3-8b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _engine(world, *, store=True, prefill_batch=1, pool_blocks=512,
            preempt_after=0):
    cfg, params = world
    spec = EngineSpec(
        strategy="cachecraft" if store else "all", use_focus=False,
        pool_blocks=pool_blocks, decode_bucket_b=4, seq_bucket=128,
        sched=SchedulerConfig(max_batch_tokens=100_000, max_decode_batch=4,
                              max_prefill_batch=prefill_batch,
                              preempt_after_iters=preempt_after))
    return build_engine(spec, cfg=cfg, params=params,
                        **({} if store else {"store": None}))


def _fresh(cfg, n, max_new=20, seed=3, reserve_full=False):
    """Requests over fresh chunks (every chunk a miss): 66 prompt tokens
    in segments of 8, 24, 24 and 10, so blocks of 16 leave 6 free slots
    after the segments (14 after a dense copy) and 19 appends open one
    block of the decode's own."""
    rng = np.random.default_rng(seed)
    V = cfg.vocab_size
    return [Request(
        rid=i, system_tokens=rng.integers(0, V, 8).astype(np.int32),
        chunk_tokens=[rng.integers(0, V, 24).astype(np.int32)
                      for _ in range(2)],
        question_tokens=rng.integers(0, V, 10).astype(np.int32),
        max_new_tokens=max_new, arrival_time=0.0,
        reserve_full=reserve_full) for i in range(n)]


def _twin_arrays(cfg, pcache):
    """The twin as host arrays: k/v [L, NBf, Hkv, D] in layer order,
    ppos [L, NBf]."""
    P, G = len(cfg.pattern), cfg.n_groups
    ks, vs, ps = [None] * cfg.num_layers, [None] * cfg.num_layers, \
        [None] * cfg.num_layers
    for p in range(P if G else 0):
        c = pcache["groups"][p]
        for g in range(G):
            ks[g * P + p] = np.asarray(c["kp"][g])
            vs[g * P + p] = np.asarray(c["vp"][g])
            ps[g * P + p] = np.asarray(c["ppos"][g])
    for i in range(cfg.n_tail):
        c = pcache["tail"][i]
        ks[G * P + i] = np.asarray(c["kp"])
        vs[G * P + i] = np.asarray(c["vp"])
        ps[G * P + i] = np.asarray(c["ppos"])
    return np.stack(ks), np.stack(vs), np.stack(ps)


def assert_twin_matches_pool(eng):
    """Twin == ``pack_paged_cache(*pool.block_view())`` bit for bit in
    every slot of every block a decoding request's table holds."""
    cfg, pool = eng.cfg, eng.pool
    bs = pool.block_size
    blocks = sorted({b for r in eng.decoding for b in r.table.blocks})
    assert blocks
    slots = (np.asarray(blocks)[:, None] * bs + np.arange(bs)).reshape(-1)
    tk, tv, tp = _twin_arrays(cfg, eng._pcache)
    hk, hv, hp = _twin_arrays(cfg, pack_paged_cache(cfg, *pool.block_view()))
    np.testing.assert_array_equal(tp[:, slots], hp[:, slots])
    np.testing.assert_array_equal(tk[:, slots], hk[:, slots])
    np.testing.assert_array_equal(tv[:, slots], hv[:, slots])


def _watch(eng):
    """Check the twin after every prefill pass once the twin exists.
    Prefill blocks must already be off the dirty log (copied on the
    device); what the host alone wrote is uploaded first, as the next
    decode step would. Returns the record of checked passes."""
    seen = {"passes": [], "prompt_blocks": 0, "private_dirty": 0,
            "reused": 0, "owners": {}}
    orig = eng._run_prefills

    def run_prefills(reqs):
        had_twin = eng._pcache is not None
        wrote = eng.counters.twin_write_blocks
        orig(reqs)
        live = [r for r in reqs if r.state == State.DECODING]
        for r in live:
            for b in r.table.blocks:
                prev = seen["owners"].get(b)
                if prev is not None and prev != r.rid:
                    seen["reused"] += 1
                seen["owners"][b] = r.rid
        if not had_twin:
            return
        assert eng.counters.twin_write_blocks > wrote
        seen["passes"].append(len(reqs))
        seen["prompt_blocks"] += sum(len(r.table.blocks) for r in live)
        # private blocks the host wrote after the device copy (CoW
        # clones for recompute fix-up rows) wait in the dirty log
        private = {b for r in live for b in r.table.blocks
                   if eng.pool.refs[b] == 1}
        seen["private_dirty"] += len(private & set(eng.pool.dirty_blocks()))
        eng._sync_dirty_blocks()
        assert_twin_matches_pool(eng)
    eng._run_prefills = run_prefills
    return seen


def _count_opened(eng):
    """Count blocks the decode path opens for appends."""
    opened = [0]
    orig = eng.pool.ensure_append_slot

    def ensure(table, reservation=None):
        n = len(table.blocks)
        out = orig(table, reservation=reservation)
        opened[0] += len(table.blocks) - n
        return out
    eng.pool.ensure_append_slot = ensure
    return opened


@pytest.mark.parametrize("mode", ["segments", "copy", "copy_reserve_full"])
def test_cold_prefill_reaches_the_twin_on_the_device(world, mode):
    """Cold single-request prefills, in each write mode: the twin
    matches the pool after every pass, the prompt blocks moved device to
    device and the uploads carried the decode-opened blocks only."""
    cfg = world[0]
    eng = _engine(world, store=mode != "copy")
    seen = _watch(eng)
    opened = _count_opened(eng)
    reqs = _fresh(cfg, 4, reserve_full=mode == "copy_reserve_full")
    eng.run(reqs)
    assert all(r.state == State.DONE for r in reqs)
    c = eng.counters
    nb = eng.pool.block_nbytes
    assert seen["passes"] == [1, 1, 1]          # the first lands pre-twin
    assert c.twin_write_blocks == seen["prompt_blocks"]
    assert c.twin_write_bytes == seen["prompt_blocks"] * nb
    assert seen["private_dirty"] == 0
    # every request's decode opened one block, each uploaded once
    assert opened[0] == len(reqs)
    assert c.paged_block_syncs == opened[0]
    assert c.paged_sync_bytes == opened[0] * nb


def test_packed_prefill_of_two_requests(world):
    """Two requests in one packed pass share one device KV: each takes
    its rows from its own layout offset."""
    cfg = world[0]
    eng = _engine(world, prefill_batch=2)
    seen = _watch(eng)
    reqs = _fresh(cfg, 6, seed=5)
    eng.run(reqs)
    assert all(r.state == State.DONE for r in reqs)
    assert 2 in seen["passes"]
    assert eng.counters.twin_write_blocks == seen["prompt_blocks"]


def test_reused_blocks_are_scrubbed(world):
    """A pool of ~3 requests' blocks under preemption: blocks freed by
    finished and preempted requests come back to new prefills, whose
    device copy overwrites every slot, tail padding included."""
    cfg = world[0]
    eng = _engine(world, pool_blocks=22, preempt_after=2)
    seen = _watch(eng)
    reqs = _fresh(cfg, 8, seed=7)
    eng.run(reqs)
    assert all(r.state == State.DONE for r in reqs)
    assert seen["reused"] > 0 and len(seen["passes"]) >= 5
    assert eng.counters.preemptions > 0
    assert eng.pool.free_blocks + eng.pool.live_blocks \
        == eng.pool.num_blocks


def test_shared_chunk_hits_keep_fixups_on_the_host_path(world):
    """Chunks recur: hits attach shared runs (loaded by the host) and
    CoW-clone blocks for recompute fix-up rows (host path, uploaded),
    while misses and questions take the device path."""
    cfg, _ = world
    kb = KnowledgeBase(num_chunks=10, vocab_size=cfg.vocab_size, seed=0)
    eng = _engine(world, prefill_batch=2)
    seen = _watch(eng)
    reqs = generate(kb, WorkloadConfig(num_requests=8, qpm=1e9, seed=11,
                                       k_chunks=3, max_new_tokens=6))
    eng.run(reqs)
    assert all(r.state == State.DONE for r in reqs)
    c = eng.counters
    assert c.shared_seg_hits > 0 and c.cow_clones > 0
    assert seen["passes"] and c.twin_write_blocks > 0
    assert c.paged_block_syncs > 0
    assert c.twin_write_blocks <= seen["prompt_blocks"]


def test_twin_programs_compile_before_first_use(world):
    """The twin's first pack compiles the write for every prefill bucket
    up to ``seq_bucket`` and the small dirty-block uploads; later
    prefills in those buckets, and the uploads of their decode-opened
    blocks, lower no program at all — not even an eager cast of the
    index rows."""
    cfg = world[0]
    jax.clear_caches()
    eng = _engine(world)
    reqs = _fresh(cfg, 4, seed=9)
    eng.submit(reqs[0])
    while eng._pcache is None:
        eng.step()
    lowered = [0]
    watching = [True]

    def on_event(name, _secs, **_kw):
        if watching[0] and name.endswith("jaxpr_to_mlir_module_duration"):
            lowered[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for r in reqs[1:]:
            eng.submit(r)
        eng.run([])
    finally:
        watching[0] = False
    assert all(r.state == State.DONE for r in reqs)
    assert eng.counters.twin_write_blocks > 0
    assert eng.counters.paged_block_syncs > 0
    assert lowered[0] == 0
