"""Paged decode, the engine's only decode path, against the references
that stay.

The paged decode path reads K/V in place from the pool's block storage
through each request's block-index rows — no per-step gather, no copy
on join. These tests pin its contracts:

* a churny join/leave schedule produces the tokens of direct greedy
  decoding with the model, with decode-opened blocks uploaded to the
  twin and churn absorbed as row-map updates;
* zero-copy shared-chunk KV (shared blocks + CoW clones) is bit
  identical to private copies of the same chunks;
* under pool pressure with preemptions and shared chunks (reclaim +
  re-prefill interleaved with paged steps) every request decodes the
  tokens of an unpressured run, logits within 1e-5;
* the step hands back each row's token and new KV in one read;
* the ``paged_kernel`` backend (Pallas, online softmax over blocks)
  tracks the ``paged`` gather route to numerical tolerance;
* a head-sharded serving mesh (subprocess on forced host devices)
  decodes the tokens of one device, logits within 1e-5.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
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


def _run(cfg, params, kb, *, strategy="all", store=False, share=True,
         pool_blocks=512, preempt_after=0, attn_impl=None,
         lengths=(3, 5, 7, 9, 4, 6), seed=11):
    spec = EngineSpec(
        strategy=strategy, use_focus=False,
        pool_blocks=pool_blocks, decode_bucket_b=4, seq_bucket=512,
        sched=SchedulerConfig(max_batch_tokens=100_000,
                              max_decode_batch=4, max_prefill_batch=2,
                              preempt_after_iters=preempt_after),
        trace_decode=True, share_chunk_kv=share, attn_impl=attn_impl)
    kw = {} if store else {"store": None}
    eng = build_engine(spec, cfg=cfg, params=params, **kw)
    reqs = _churny_requests(kb, lengths, seed)
    stats = eng.run(reqs)
    return eng, stats, reqs


def _assert_bit_identical(eng_a, eng_p):
    """Per-step decode logits and final pool KV, bit for bit. The KV is
    compared token by token: block-aligned segments leave padding
    (position -1) inside a table that a dense write does not."""
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
        ka, va, pa = _tokens_only(*eng_a.final_kv[rid])
        kp, vp, pp = _tokens_only(*eng_p.final_kv[rid])
        np.testing.assert_array_equal(pa, pp)
        np.testing.assert_array_equal(ka, kp)
        np.testing.assert_array_equal(va, vp)


def _tokens_only(k, v, pos):
    """A gathered table's K, V and positions with the padding dropped."""
    keep = pos >= 0
    return k[:, keep], v[:, keep], pos[keep]


def _greedy_reference(cfg, params, req, n):
    """``n`` tokens of direct greedy decoding with the model over the
    request's whole prompt (no executor, no pool)."""
    prompt = np.concatenate([req.system_tokens, *req.chunk_tokens,
                             req.question_tokens])
    S = len(prompt)
    pre = M.prefill(cfg, params, tokens=jnp.asarray(prompt[None]),
                    cache_len=S + n, ring=False)
    toks = [int(np.argmax(np.asarray(pre.logits[0, -1, :cfg.vocab_size])))]
    cache = pre.cache
    for i in range(n - 1):
        out = M.decode_step(cfg, params, jnp.asarray([toks[-1]]),
                            jnp.asarray([S + i], jnp.int32), cache)
        cache = out.cache
        toks.append(int(np.argmax(np.asarray(
            out.logits[0, 0, :cfg.vocab_size]))))
    return toks


def test_paged_matches_arena_churny(world):
    """A churny schedule, one request decoding past its prompt's last
    block: every request's tokens are those of direct greedy decoding
    with the model."""
    cfg, params, kb = world
    eng, stats, reqs = _run(cfg, params, kb, lengths=(3, 5, 7, 20, 4, 6))

    assert stats.completed == 6 and stats.failed == 0
    for r in reqs:
        assert r.state == State.DONE
        assert r.output_tokens == _greedy_reference(
            cfg, params, r, r.max_new_tokens), f"rid {r.rid}"

    # prefill blocks reached the twin on the device; the block the long
    # decode opened was uploaded from the dirty log
    c = eng.counters
    assert c.twin_write_blocks > 0
    assert c.paged_block_syncs > 0

    # churn was absorbed as row-map updates, not rebuilds
    assert c.decode_rebuilds == 1
    assert c.decode_joins >= 4
    assert c.decode_leaves >= 5

    # pool fully settled
    assert eng.pool.live_blocks == 0 and eng.pool.reserved_blocks == 0
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_paged_matches_arena_shared_chunks(world):
    """With a chunk store, zero-copy sharing reads shared blocks in
    place and CoW-clones on decode writes; bit identical to the same
    workload with every chunk copied into private blocks."""
    cfg, params, kb = world
    eng_c, stats_c, _ = _run(cfg, params, kb, strategy="cachecraft",
                             store=True, share=False)
    eng_s, stats_s, _ = _run(cfg, params, kb, strategy="cachecraft",
                             store=True)

    assert stats_c.completed == 6 and stats_c.failed == 0
    assert stats_s.completed == 6 and stats_s.failed == 0
    _assert_bit_identical(eng_c, eng_s)

    # the schedule actually exercised sharing + CoW; the blocks only
    # the host wrote (shared-run loads, CoW clones) reached the twin by
    # upload, the prefills' private blocks on the device
    assert not eng_c.share_chunk_kv and eng_s.share_chunk_kv
    assert eng_c.counters.shared_seg_hits == 0
    assert eng_s.counters.shared_seg_hits > 0
    assert eng_s.pool.counters.cow_clones > 0
    assert eng_s.counters.paged_block_syncs > 0
    assert eng_s.counters.twin_write_blocks > 0


def test_paged_matches_arena_under_preemption(world):
    """Pool-starved run with preemptions and shared chunks: reclaim
    tears down block-index rows and releases shared runs mid-flight,
    and re-prefills re-enter the batch. Every request decodes the
    tokens of an unpressured run, final logits within 1e-5 (a
    re-prefill runs at another packed shape, see
    ``test_engine_incremental``)."""
    cfg, params, kb = world
    lengths = (18, 18, 3, 5, 4, 6)
    runs = [_run(cfg, params, kb, strategy="cachecraft", store=True,
                 pool_blocks=blocks, preempt_after=after, lengths=lengths,
                 seed=17) for blocks, after in ((512, 0), (20, 4))]
    (eng_u, stats_u, reqs_u), (eng_p, stats_p, reqs_p) = runs

    assert stats_u.completed == 6 and stats_u.failed == 0
    assert stats_p.completed == 6 and stats_p.failed == 0
    assert eng_u.counters.preemptions == 0
    assert eng_p.counters.preemptions > 0
    assert eng_p.counters.shared_seg_hits > 0
    for ru, rp in zip(reqs_u, reqs_p):
        assert ru.output_tokens == rp.output_tokens, f"rid {ru.rid}"

    last_u, last_p = {}, {}
    for trace, last in ((eng_u.decode_trace, last_u),
                        (eng_p.decode_trace, last_p)):
        for step in trace:
            last.update(step)
    assert set(last_u) == set(last_p)
    for rid in last_u:
        np.testing.assert_allclose(last_p[rid], last_u[rid], rtol=1e-5,
                                   atol=1e-5, err_msg=f"rid {rid}")
    live = sum(len(r.blocks) for r in eng_p.store.residency.runs.values())
    assert eng_p.pool.reserved_blocks == 0
    assert eng_p.pool.live_blocks == live


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
        trace_decode=True)
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
    over pool blocks, against the ``paged`` gather route. Block-order
    accumulation differs from the dense reduction, so the gate is
    numerical closeness per step. The (8, 1) case is the 67B share's
    grouped-query ratio: 8 query heads over one KV head, prefill then
    up to 8 paged decode steps, logits compared."""
    cfg, params, kb = world
    if heads is not None:
        cfg = cfg.replace(num_heads=heads[0], num_kv_heads=heads[1])
        params = M.init_params(cfg, jax.random.PRNGKey(1))
    eng_a, stats_a, _ = _run(cfg, params, kb, attn_impl="paged")
    eng_k, stats_k, _ = _run(cfg, params, kb, attn_impl="paged_kernel")

    assert not eng_a._paged_kernel and eng_k._paged_kernel
    assert stats_a.completed == 6 and stats_a.failed == 0
    assert stats_k.completed == 6 and stats_k.failed == 0
    assert len(eng_k.decode_trace) == len(eng_a.decode_trace)
    for step, (ta, tk) in enumerate(zip(eng_a.decode_trace,
                                        eng_k.decode_trace)):
        assert set(ta) == set(tk), f"step {step}: membership differs"
        for rid in ta:
            np.testing.assert_allclose(
                tk[rid], ta[rid], rtol=2e-4, atol=2e-4,
                err_msg=f"step {step}, rid {rid}")


def test_paged_sharded_mesh_bit_identical():
    """Head-sharded serving mesh + paged decode, subprocess on 4 forced
    host devices, against one device: equal tokens, per-step logits and
    final pool KV within 1e-5 (each shard reduces over its own heads,
    and XLA picks its reduction blocking per operand shape)."""
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

def run(mesh):
    AB.set_serving_mesh(None)
    eng = build_engine(
        EngineSpec(strategy="all", use_focus=False, pool_blocks=1024,
                   sched=SchedulerConfig(max_batch_tokens=100_000,
                                         max_decode_batch=8,
                                         max_prefill_batch=4),
                   trace_decode=True, mesh=mesh),
        cfg=cfg, params=params, store=None)
    reqs = generate(kb, wl)
    stats = eng.run(reqs)
    assert stats.completed == 4 and stats.failed == 0, \
        (stats.completed, stats.failed)
    return eng, reqs

e1, r1 = run(None)
e2, r2 = run(make_serving_mesh(4))
assert e1.kv_shards == 1 and e2.kv_shards == 4
for a, b in zip(r1, r2):
    assert a.output_tokens == b.output_tokens
assert len(e1.decode_trace) == len(e2.decode_trace) > 0
for da, db in zip(e1.decode_trace, e2.decode_trace):
    assert set(da) == set(db)
    for rid in da:
        np.testing.assert_allclose(db[rid], da[rid], rtol=1e-5, atol=1e-5)
assert set(e1.final_kv) == set(e2.final_kv)
for rid in e1.final_kv:
    (k1, v1, p1), (k2, v2, p2) = e1.final_kv[rid], e2.final_kv[rid]
    assert np.array_equal(p1, p2), rid
    np.testing.assert_allclose(k2, k1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v2, v1, rtol=1e-5, atol=1e-5)
print("PAGED_SHARDED_EQ_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=os.getcwd(),
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PAGED_SHARDED_EQ_OK" in r.stdout
