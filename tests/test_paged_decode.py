"""Paged decode vs arena decode: the bit-identity gates.

The paged decode path reads K/V in place from the pool's block storage
through each request's block-index rows — no per-step gather, no arena
copy on join. These tests pin the tentpole contract:

* a churny join/leave schedule run paged must produce per-step decode
  logits AND final pool KV bit-identical to the arena path, while
  ``decode_gather_bytes`` / ``decode_join_copies`` drop to zero;
* the same holds with a chunk store and shared-chunk KV (zero-copy
  shared blocks + CoW clones in the schedule);
* and under pool pressure with preemptions (reclaim + re-prefill
  interleaved with paged steps);
* the ``paged_kernel`` backend (Pallas, online softmax over blocks)
  tracks the same trajectory to numerical tolerance;
* a head-sharded serving mesh composes with paged decode (subprocess
  on forced host devices), still bit-identical to the arena run.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_tiny
from repro.models import model as M
from repro.serving.api import EngineSpec, build_engine
from repro.serving.rag import KnowledgeBase
from repro.serving.request import State
from repro.serving.scheduler import SchedulerConfig
from repro.serving.workload import WorkloadConfig, generate


@pytest.fixture(scope="module")
def world():
    cfg = get_tiny("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    kb = KnowledgeBase(num_chunks=10, vocab_size=cfg.vocab_size, seed=0)
    return cfg, params, kb


def _churny_requests(kb, lengths=(3, 5, 7, 9, 4, 6), seed=11):
    """All-at-once arrivals, varied decode lengths: with one admission
    per iteration the decode batch churns on most steps."""
    wl = WorkloadConfig(num_requests=len(lengths), qpm=1e9, seed=seed,
                        k_chunks=3, max_new_tokens=4)
    reqs = generate(kb, wl)
    for r, n in zip(reqs, lengths):
        r.max_new_tokens = n
    return reqs


def _run(cfg, params, kb, *, paged, strategy="all", store=False,
         pool_blocks=512, preempt_after=0, attn_impl=None,
         lengths=(3, 5, 7, 9, 4, 6), seed=11):
    spec = EngineSpec(
        strategy=strategy, use_focus=False,
        pool_blocks=pool_blocks, decode_bucket_b=4, seq_bucket=512,
        sched=SchedulerConfig(max_batch_tokens=100_000,
                              max_decode_batch=4, max_prefill_batch=2,
                              preempt_after_iters=preempt_after),
        trace_decode=True, paged_decode=paged, attn_impl=attn_impl)
    kw = {} if store else {"store": None}
    eng = build_engine(spec, cfg=cfg, params=params, **kw)
    reqs = _churny_requests(kb, lengths, seed)
    stats = eng.run(reqs)
    return eng, stats, reqs


def _assert_bit_identical(eng_a, eng_p):
    """Per-step decode logits and final pool KV, bit for bit."""
    assert len(eng_a.decode_trace) == len(eng_p.decode_trace) > 0
    for step, (ta, tp) in enumerate(zip(eng_a.decode_trace,
                                        eng_p.decode_trace)):
        assert set(ta) == set(tp), f"step {step}: membership differs"
        for rid in ta:
            np.testing.assert_array_equal(
                ta[rid], tp[rid],
                err_msg=f"step {step}, rid {rid}: decode logits differ")
    assert set(eng_a.final_kv) == set(eng_p.final_kv)
    for rid in eng_a.final_kv:
        ka, va, pa = eng_a.final_kv[rid]
        kp, vp, pp = eng_p.final_kv[rid]
        np.testing.assert_array_equal(pa, pp)
        np.testing.assert_array_equal(ka, kp)
        np.testing.assert_array_equal(va, vp)


def test_paged_matches_arena_churny(world):
    cfg, params, kb = world
    eng_a, stats_a, reqs_a = _run(cfg, params, kb, paged=False)
    eng_p, stats_p, reqs_p = _run(cfg, params, kb, paged=True)

    assert stats_a.completed == 6 and stats_a.failed == 0
    assert stats_p.completed == 6 and stats_p.failed == 0
    for ra, rp in zip(reqs_a, reqs_p):
        assert ra.state == State.DONE and rp.state == State.DONE
        assert ra.output_tokens == rp.output_tokens

    _assert_bit_identical(eng_a, eng_p)

    # the point of the tentpole: the arena path copies KV on every
    # rebuild/join; the paged path moves ZERO gather bytes — its only
    # traffic is block-granular writes into the pool twin: prefill
    # blocks copied device to device, plus uploads of blocks only the
    # host wrote (none in this schedule: no shared runs or CoW, and
    # every request's decode fits in its prompt's last block)
    ca, cp = eng_a.counters, eng_p.counters
    assert ca.decode_gather_bytes > 0
    assert ca.decode_join_copies > 0
    assert cp.decode_gather_bytes == 0
    assert cp.decode_join_copies == 0
    assert cp.twin_write_blocks > 0
    assert cp.paged_block_syncs == 0
    assert cp.paged_sync_bytes + cp.twin_write_bytes \
        < ca.decode_gather_bytes

    # churn was absorbed as row-map updates, not rebuild+gather
    assert cp.decode_joins >= 4
    assert cp.decode_leaves >= 5

    # pool fully settled
    assert eng_p.pool.live_blocks == 0 and eng_p.pool.reserved_blocks == 0
    assert eng_p.pool.free_blocks == eng_p.pool.num_blocks


def test_paged_matches_arena_shared_chunks(world):
    """With a chunk store and shared-chunk KV the paged path reads
    shared blocks in place and CoW-clones on decode writes; still bit
    identical to the arena run of the same workload."""
    cfg, params, kb = world
    eng_a, stats_a, _ = _run(cfg, params, kb, paged=False,
                             strategy="cachecraft", store=True)
    eng_p, stats_p, _ = _run(cfg, params, kb, paged=True,
                             strategy="cachecraft", store=True)

    assert stats_a.completed == 6 and stats_a.failed == 0
    assert stats_p.completed == 6 and stats_p.failed == 0
    _assert_bit_identical(eng_a, eng_p)

    # the schedule actually exercised sharing + CoW under paged decode;
    # the blocks only the host wrote (shared-run loads, CoW clones)
    # reached the twin by upload, the prefills' private blocks on the
    # device
    assert eng_p.pool.counters.cow_clones > 0
    assert eng_p.counters.decode_gather_bytes == 0
    assert eng_p.counters.paged_block_syncs > 0
    assert eng_p.counters.twin_write_blocks > 0


def test_paged_matches_arena_under_preemption(world):
    """Pool-starved run with preemptions: reclaim tears down block-index
    rows mid-flight and re-prefills re-enter the paged batch; the whole
    pressured trajectory must stay bit-identical to the arena engine
    under the same pressure."""
    cfg, params, kb = world
    lengths = (18, 18, 3, 5, 4, 6)
    eng_a, stats_a, reqs_a = _run(cfg, params, kb, paged=False,
                                  pool_blocks=20, preempt_after=4,
                                  lengths=lengths, seed=17)
    eng_p, stats_p, reqs_p = _run(cfg, params, kb, paged=True,
                                  pool_blocks=20, preempt_after=4,
                                  lengths=lengths, seed=17)

    assert stats_a.completed == 6 and stats_a.failed == 0
    assert stats_p.completed == 6 and stats_p.failed == 0
    assert eng_a.counters.preemptions > 0
    assert eng_p.counters.preemptions == eng_a.counters.preemptions
    for ra, rp in zip(reqs_a, reqs_p):
        assert ra.output_tokens == rp.output_tokens

    _assert_bit_identical(eng_a, eng_p)
    assert eng_p.counters.decode_gather_bytes == 0
    assert eng_p.pool.free_blocks == eng_p.pool.num_blocks


def _twin_slot_kv(cfg, twin, slot):
    """Layer-ordered K and V ``[L, Hkv, D]`` at one flat twin slot."""
    P, G = len(cfg.pattern), cfg.n_groups
    k = [None] * cfg.num_layers
    v = [None] * cfg.num_layers
    for p, c in enumerate(twin["groups"]):
        for g in range(G):
            k[g * P + p] = c["kp"][g, slot]
            v[g * P + p] = c["vp"][g, slot]
    for i, c in enumerate(twin["tail"]):
        k[G * P + i], v[G * P + i] = c["kp"][slot], c["vp"][slot]
    return np.stack(k), np.stack(v)


def test_paged_step_returns_tokens_and_new_kv(world):
    """The paged step hands back each row's token and new KV in one
    read. Over decode-only steps with 3 live rows beside a masked one,
    on a config with two pattern layers, a tail layer and a padded
    vocabulary whose padding columns would win an unmasked argmax: (a)
    each emitted token is ``np.argmax`` of the step's logits over
    ``vocab_size``; (b) the host pool's K/V at every slot the step wrote
    equal the twin's bit for bit; (c) none of those blocks stays in the
    dirty log."""
    _cfg, _params, kb = world
    cfg = _cfg.replace(vocab_size=500, pattern=("attn", "attn"),
                       num_layers=5)
    assert cfg.padded_vocab > cfg.vocab_size and cfg.n_tail == 1
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    pad = 100 * jax.random.normal(
        jax.random.PRNGKey(3), (cfg.d_model, cfg.padded_vocab - 500))
    params["unembed"] = params["unembed"].at[:, 500:].set(pad)
    spec = EngineSpec(
        strategy="all", use_focus=False, pool_blocks=64,
        decode_bucket_b=4, seq_bucket=512,
        sched=SchedulerConfig(max_batch_tokens=100_000,
                              max_decode_batch=4, max_prefill_batch=4),
        trace_decode=True, paged_decode=True)
    eng = build_engine(spec, cfg=cfg, params=params, store=None)
    reqs = _churny_requests(kb, lengths=(12, 3, 12, 12))
    for r in reqs:
        eng.submit(r)
    while eng.scheduler.queue:
        eng.step()
    pool, bs = eng.pool, eng.pool.block_size
    written = []
    append = pool.append_token

    def recording_append(table, *a, **kw):
        ok = append(table, *a, **kw)
        n = table.length - 1
        written.append(table.blocks[n // bs] * bs + n % bs)
        return ok
    pool.append_token = recording_append

    masked_steps = padded_wins = 0
    while eng.decoding:
        before = {r.rid: len(r.output_tokens) for r in eng.decoding}
        live = len(eng.decoding)
        masked_steps += live >= 3 and None in eng._rows
        written.clear()
        eng.step()
        assert not eng.scheduler.queue and len(written) == live
        logits = eng.decode_trace[-1]
        assert set(logits) == set(before)
        for r in reqs:
            if r.rid in before:
                row = logits[r.rid]
                assert r.output_tokens[before[r.rid]] == \
                    int(np.argmax(row[:cfg.vocab_size]))
                padded_wins += int(np.argmax(row)) >= cfg.vocab_size
        twin = jax.tree.map(np.asarray, eng._pcache)
        for slot in written:
            b, off = divmod(slot, bs)
            kt, vt = _twin_slot_kv(cfg, twin, slot)
            np.testing.assert_array_equal(
                pool.k[:, b, off].view(np.uint32), kt.view(np.uint32))
            np.testing.assert_array_equal(
                pool.v[:, b, off].view(np.uint32), vt.view(np.uint32))
        assert not {s // bs for s in written} & set(pool.dirty_blocks())
    assert masked_steps >= 2 and padded_wins > 0
    assert all(r.state == State.DONE for r in reqs)


@pytest.mark.parametrize("heads", [None, (8, 1)], ids=["tiny", "h8_kv1"])
def test_paged_kernel_backend_tracks_reference(world, heads):
    """attn_impl="paged_kernel" routes the Pallas online-softmax kernel
    over pool blocks. Block-order accumulation differs from the dense
    reduction, so the gate is numerical closeness per step — plus the
    same zero-gather counters. The (8, 1) case is the 67B share's
    grouped-query ratio: 8 query heads over one KV head, prefill then
    up to 8 paged decode steps, logits compared."""
    cfg, params, kb = world
    if heads is not None:
        cfg = cfg.replace(num_heads=heads[0], num_kv_heads=heads[1])
        params = M.init_params(cfg, jax.random.PRNGKey(1))
    eng_a, stats_a, _ = _run(cfg, params, kb, paged=False)
    eng_k, stats_k, _ = _run(cfg, params, kb, paged=True,
                             attn_impl="paged_kernel")

    assert stats_k.completed == 6 and stats_k.failed == 0
    assert len(eng_k.decode_trace) == len(eng_a.decode_trace)
    for step, (ta, tk) in enumerate(zip(eng_a.decode_trace,
                                        eng_k.decode_trace)):
        assert set(ta) == set(tk), f"step {step}: membership differs"
        for rid in ta:
            np.testing.assert_allclose(
                tk[rid], ta[rid], rtol=2e-4, atol=2e-4,
                err_msg=f"step {step}, rid {rid}")
    assert eng_k.counters.decode_gather_bytes == 0


def test_paged_sharded_mesh_bit_identical():
    """Head-sharded serving mesh + paged decode, subprocess on 4 forced
    host devices: paged run bit-identical to the arena run on the same
    mesh, with zero gather bytes."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "src")
import jax, numpy as np
from repro.configs import get_tiny
from repro.models import model as M
from repro.models import backend as AB
from repro.launch.mesh import make_serving_mesh
from repro.serving.api import EngineSpec, build_engine
from repro.serving.rag import KnowledgeBase
from repro.serving.scheduler import SchedulerConfig
from repro.serving.workload import WorkloadConfig, generate

cfg = get_tiny("llama3-8b").replace(num_heads=4, num_kv_heads=4)
params = M.init_params(cfg, jax.random.PRNGKey(0))
kb = KnowledgeBase(num_chunks=8, vocab_size=cfg.vocab_size, seed=0)
wl = WorkloadConfig(num_requests=4, qpm=1e9, seed=3, max_new_tokens=4)

def run(paged):
    AB.set_serving_mesh(None)
    eng = build_engine(
        EngineSpec(strategy="all", use_focus=False, pool_blocks=1024,
                   sched=SchedulerConfig(max_batch_tokens=100_000,
                                         max_decode_batch=8,
                                         max_prefill_batch=4),
                   trace_decode=True, paged_decode=paged,
                   mesh=make_serving_mesh(4)),
        cfg=cfg, params=params, store=None)
    reqs = generate(kb, wl)
    stats = eng.run(reqs)
    assert stats.completed == 4 and stats.failed == 0, \
        (stats.completed, stats.failed)
    return eng, reqs

e1, r1 = run(False)
e2, r2 = run(True)
assert e1.kv_shards == 4 and e2.kv_shards == 4
for a, b in zip(r1, r2):
    assert a.output_tokens == b.output_tokens
assert len(e1.decode_trace) == len(e2.decode_trace) > 0
for da, db in zip(e1.decode_trace, e2.decode_trace):
    assert set(da) == set(db)
    for rid in da:
        assert np.array_equal(da[rid], db[rid]), rid   # BIT equality
assert set(e1.final_kv) == set(e2.final_kv)
for rid in e1.final_kv:
    for x, y in zip(e1.final_kv[rid], e2.final_kv[rid]):
        assert np.array_equal(x, y), rid
assert e2.counters.decode_gather_bytes == 0
assert e1.counters.decode_gather_bytes > 0
print("PAGED_SHARDED_EQ_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=os.getcwd(),
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PAGED_SHARDED_EQ_OK" in r.stdout
