"""Online serving front end + typed construction API (ISSUE 8 gates).

* ``EngineSpec`` validation fails fast with the offending field named;
  ``from_args`` maps the launcher flag surface (``--full`` replacing
  the unreachable-full ``--tiny``, ``tier_dtypes`` string parsing)
* ``build_engine`` is bit-equivalent to the deprecated
  ``executor_kwargs`` construction path, which must warn
* cancellation at every lifecycle point: mid-queue (scheduler removal +
  prefetch-ticket retraction), mid-decode (row masked, shared-run
  readers released, pool conservation holds, the surviving request's
  output stays bit-identical to an uncancelled run)
* per-token streaming: tokens arrive incrementally across engine steps
  and concatenate to exactly the non-streamed output
* the HTTP server end-to-end: submit/stream/cancel/health/stats over a
  real socket, streamed tokens bit-identical to ``Engine.run``
* session-structured workloads: independent per-session prefixes,
  multi-turn history growth, deterministic tenant assignment, and the
  determinism contract (single-turn configs leave the legacy main-rng
  stream untouched)
"""
import threading
from argparse import Namespace

import jax
import numpy as np
import pytest

from repro.configs import get_tiny
from repro.models import model as M
from repro.serving.api import (EngineSpec, StoreSpec, build_engine,
                               build_store)
from repro.serving.engine import Engine
from repro.serving.rag import KnowledgeBase
from repro.serving.request import Request, State
from repro.serving.scheduler import SchedulerConfig
from repro.serving.workload import (TenantSpec, WorkloadConfig,
                                    generate)


@pytest.fixture(scope="module")
def world():
    cfg = get_tiny("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    kb = KnowledgeBase(num_chunks=12, vocab_size=cfg.vocab_size, seed=0)
    return cfg, params, kb


def _spec(**kw):
    kw.setdefault("strategy", "all")
    kw.setdefault("use_focus", False)
    kw.setdefault("pool_blocks", 512)
    kw.setdefault("sched", SchedulerConfig(max_batch_tokens=100_000,
                                           max_decode_batch=4,
                                           max_prefill_batch=1))
    return EngineSpec(**kw)


def _requests(kb, n=2, max_new=4, seed=5, shared_chunks=False):
    rng = np.random.default_rng(seed)
    V = kb.vocab_size
    chunks = [kb.chunks[0], kb.chunks[1]]
    out = []
    for i in range(n):
        if not shared_chunks:
            chunks = [kb.chunks[(2 * i) % len(kb.chunks)],
                      kb.chunks[(2 * i + 1) % len(kb.chunks)]]
        out.append(Request(
            rid=i, system_tokens=rng.integers(0, V, 8).astype(np.int32),
            chunk_tokens=[c.copy() for c in chunks],
            question_tokens=rng.integers(0, V, 10).astype(np.int32),
            max_new_tokens=max_new, arrival_time=0.0))
    return out


# ---- EngineSpec validation ---------------------------------------------------
@pytest.mark.parametrize("kw,err,match", [
    (dict(strategy="nope"), ValueError, "strategy"),
    (dict(attn_impl="nope"), ValueError, "attn_impl"),
    (dict(pool_blocks=0), ValueError, "pool_blocks"),
    (dict(force_recompute_fraction=1.5), ValueError,
     "force_recompute_fraction"),
    (dict(sched={"max_decode_batch": 4}), TypeError, "sched"),
    (dict(store=StoreSpec(tier_dtypes={"cpu": "int4"})), ValueError,
     "tier_dtypes"),
    (dict(store=StoreSpec(hbm_bytes=0)), ValueError, "capacities"),
    (dict(store={"n_chunks": 5}), TypeError, "store"),
    (dict(paged_decode=False), ValueError, "paged_decode"),
])
def test_spec_validation_names_the_field(kw, err, match):
    with pytest.raises(err, match=match):
        EngineSpec(**kw).validate()


def test_spec_from_args_flag_surface():
    # empty namespace -> pure defaults (every flag optional)
    spec = EngineSpec.from_args(Namespace())
    assert spec.tiny and spec.use_focus
    assert spec.strategy == "cachecraft" and spec.store is not None

    spec = EngineSpec.from_args(Namespace(
        full=True, no_focus=True, strategy="cachecraft", recompute=0.3,
        pool_blocks=2048, max_batch_tokens=4096, max_decode_batch=8,
        tier_dtypes="cpu=int8, ssd=fp8"))
    assert spec.tiny is False          # --full reachable again
    assert spec.use_focus is False
    assert spec.force_recompute_fraction == 0.3
    assert spec.pool_blocks == 2048
    assert spec.sched.max_batch_tokens == 4096
    assert spec.sched.max_decode_batch == 8
    assert spec.store.tier_dtypes == {"cpu": "int8", "ssd": "fp8"}

    # full recompute never takes a store
    assert EngineSpec.from_args(Namespace(strategy="all")).store is None

    with pytest.raises(ValueError, match="strategy"):
        EngineSpec.from_args(Namespace(strategy="bogus"))


def test_build_store_respects_spec(tmp_path):
    store = build_store(StoreSpec(ssd_dir=str(tmp_path / "s"),
                                  n_chunks=7, m_variants=2,
                                  start_worker=False))
    assert store.n_chunks == 7 and store.m_variants == 2
    assert build_store(None) is None


# ---- deprecated executor_kwargs alias ---------------------------------------
def test_executor_kwargs_deprecated_but_equivalent(world):
    cfg, params, kb = world
    reqs_new = _requests(kb)
    reqs_old = _requests(kb)
    eng_new = build_engine(_spec(), cfg=cfg, params=params, store=None)
    with pytest.warns(DeprecationWarning, match="executor_kwargs"):
        eng_old = Engine(
            cfg, params, None,
            sched=SchedulerConfig(max_batch_tokens=100_000,
                                  max_decode_batch=4,
                                  max_prefill_batch=1),
            pool_blocks=512,
            executor_kwargs=dict(strategy="all", use_focus=False))
    eng_new.run(reqs_new)
    eng_old.run(reqs_old)
    for a, b in zip(reqs_new, reqs_old):
        assert a.state == State.DONE
        assert a.output_tokens == b.output_tokens


# ---- cancellation ------------------------------------------------------------
def test_cancel_mid_queue_retracts_prefetch(world, tmp_path):
    cfg, params, kb = world
    store = build_store(StoreSpec(ssd_dir=str(tmp_path / "s"),
                                  n_chunks=50, m_variants=4,
                                  start_worker=False))
    eng = build_engine(_spec(strategy="cachecraft"), cfg=cfg,
                       params=params, store=store)
    reqs = _requests(kb, n=3)
    for r in reqs:
        eng.submit(r)
    eng.step()                  # admits reqs[0]; lookahead prefetches
    victim = reqs[2]
    assert victim.state == State.QUEUED
    ticket = victim.prefetch_ticket
    assert ticket is not None and not ticket.cancelled

    before = eng.counters.prefetch_cancels
    assert eng.cancel(victim.rid)
    assert victim.state == State.CANCELLED
    assert ticket.cancelled                   # promotions retracted
    assert victim.prefetch_ticket is None
    assert eng.counters.prefetch_cancels == before + 1
    assert all(r.rid != victim.rid for r in eng.scheduler.queue)
    # cancelling a terminal request is a no-op, not an error
    assert not eng.cancel(victim.rid)

    eng.step_until_idle()
    assert all(r.state == State.DONE for r in reqs[:2])
    assert eng.stats.cancelled == 1
    p = eng.pool
    assert p.reserved_blocks == 0
    assert p.free_blocks + p.live_blocks == p.num_blocks


def test_cancel_mid_decode_conserves_and_keeps_survivor_bits(world,
                                                             tmp_path):
    """Cancel one of two decoding requests that SHARE chunk blocks:
    the row is masked, the shared-run reader ref released, pool
    conservation holds, and the survivor's output stays bit-identical
    to a run where nothing was cancelled."""
    cfg, params, kb = world

    def make(tag):
        store = build_store(StoreSpec(ssd_dir=str(tmp_path / tag),
                                      n_chunks=50, m_variants=4,
                                      start_worker=False))
        eng = build_engine(
            _spec(strategy="cachecraft",
                  sched=SchedulerConfig(max_batch_tokens=100_000,
                                        max_decode_batch=4,
                                        max_prefill_batch=2)),
            cfg=cfg, params=params, store=store)
        return eng, _requests(kb, n=2, max_new=8, shared_chunks=True)

    # reference: both run to completion
    ref_eng, ref_reqs = make("ref")
    ref_eng.run(ref_reqs)
    assert all(r.state == State.DONE for r in ref_reqs)

    eng, reqs = make("cut")
    for r in reqs:
        eng.submit(r)
    for _ in range(64):
        eng.step()
        if all(r.state == State.DECODING for r in reqs):
            break
    assert all(r.state == State.DECODING for r in reqs)

    eng.request_cancel(reqs[0].rid)     # thread-safe flag...
    eng.step()                          # ...applied at the next step
    assert reqs[0].state == State.CANCELLED
    assert len(reqs[0].output_tokens) < reqs[0].max_new_tokens
    eng.step_until_idle()

    assert reqs[1].state == State.DONE
    assert reqs[1].output_tokens == ref_reqs[1].output_tokens
    # cancelled prefix matches the uncancelled run bit-for-bit
    n = len(reqs[0].output_tokens)
    assert reqs[0].output_tokens == ref_reqs[0].output_tokens[:n]
    p = eng.pool
    assert p.reserved_blocks == 0
    assert p.free_blocks + p.live_blocks == p.num_blocks


def test_cancel_unknown_rid_is_noop(world):
    cfg, params, _kb = world
    eng = build_engine(_spec(), cfg=cfg, params=params, store=None)
    assert not eng.cancel(999)
    eng.request_cancel(999)
    eng.step()                          # pending cancel of unknown rid
    assert eng.stats.cancelled == 0


# ---- per-token streaming -----------------------------------------------------
def test_streaming_incremental_and_bit_exact(world):
    cfg, params, kb = world
    ref_eng = build_engine(_spec(), cfg=cfg, params=params, store=None)
    ref = _requests(kb, n=2, max_new=6)
    ref_eng.run(ref)

    eng = build_engine(_spec(), cfg=cfg, params=params, store=None)
    reqs = _requests(kb, n=2, max_new=6)
    for r in reqs:
        eng.submit(r)
    streamed = {r.rid: [] for r in reqs}
    drains_with_tokens = 0
    for _ in range(256):
        if not eng.step():
            break
        ev = eng.drain_tokens()
        drains_with_tokens += bool(ev)
        for rid, tok in ev:
            streamed[rid].append(tok)
    # tokens arrived incrementally (many small drains), not in one burst
    assert drains_with_tokens > 1
    for r, rr in zip(reqs, ref):
        assert r.state == State.DONE
        assert streamed[r.rid] == r.output_tokens == rr.output_tokens


def test_requeue_does_not_duplicate_streamed_tokens(world):
    """Regression: a preemption/requeue cleared ``output_tokens`` and
    the retry re-ran prefill+decode, so ``_emit_token`` re-emitted the
    already-streamed prefix — HTTP clients saw duplicated tokens under
    pool pressure. The ``tokens_emitted`` watermark survives
    ``reset_attempt`` and suppresses the replayed indices."""
    cfg, params, kb = world
    eng = build_engine(_spec(), cfg=cfg, params=params, store=None)
    r = _requests(kb, n=1, max_new=6)[0]
    eng.submit(r)
    streamed = []
    for _ in range(64):
        eng.step()
        streamed += [t for _, t in eng.drain_tokens()]
        if r.state == State.DECODING and len(r.output_tokens) >= 3:
            break
    assert r.state == State.DECODING and len(streamed) >= 3

    eng._preempt(r)                  # burns the attempt mid-decode
    assert r.output_tokens == [] and r.tokens_emitted == len(streamed)
    eng.scheduler.preempt_requeue(r)   # the path step() takes
    eng.step_until_idle()
    streamed += [t for _, t in eng.drain_tokens()]

    assert r.state == State.DONE
    assert len(r.output_tokens) == r.max_new_tokens
    # the stream saw each output index exactly once, no replayed prefix
    assert streamed == r.output_tokens


# ---- stats payload -----------------------------------------------------------
def test_stats_dict_shape(world):
    cfg, params, kb = world
    eng = build_engine(_spec(), cfg=cfg, params=params, store=None)
    eng.run(_requests(kb))
    d = eng.stats_dict()
    assert d["completed"] == 2 and d["failed"] == 0
    assert d["cancelled"] == 0
    assert "decode_rebuilds" in d["counters"]
    pool = d["pool"]
    assert pool["free_blocks"] + pool["live_blocks"] \
        + pool["reserved_blocks"] == pool["num_blocks"]


# ---- HTTP server end-to-end --------------------------------------------------
def test_http_server_end_to_end(world):
    from repro.serving.server import CacheCraftServer, ServeClient
    cfg, params, kb = world
    ref_eng = build_engine(_spec(), cfg=cfg, params=params, store=None)
    ref = _requests(kb, n=3, max_new=5)
    ref_eng.run(ref)

    eng = build_engine(_spec(), cfg=cfg, params=params, store=None)
    server = CacheCraftServer(eng)
    server.start()
    try:
        client = ServeClient(server.host, server.port)
        assert client.health()["ok"]

        streams, states = {}, {}

        def reader(rid):
            streams[rid], states[rid] = client.stream(rid)

        threads = []
        for req in _requests(kb, n=3, max_new=5):
            rid = client.submit(req)
            t = threading.Thread(target=reader, args=(rid,),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)

        for rid, rr in enumerate(ref):
            assert states[rid] == State.DONE.value
            assert streams[rid] == rr.output_tokens   # bit-identical

        stats = client.stats()
        assert stats["server"]["submitted"] == 3
        assert stats["server"]["inflight"] == 0
        assert stats["pool"]["reserved_blocks"] == 0
        assert "tenants" in stats
    finally:
        server.shutdown()


def test_http_cancel_mid_decode(world):
    from repro.serving.server import CacheCraftServer, ServeClient
    cfg, params, kb = world
    eng = build_engine(_spec(), cfg=cfg, params=params, store=None)
    server = CacheCraftServer(eng)
    server.start()
    try:
        client = ServeClient(server.host, server.port)
        req = _requests(kb, n=1, max_new=64)[0]
        rid = client.submit(req)
        acc = []

        def on_token(tok):
            acc.append(tok)
            if len(acc) == 2:
                client.cancel(rid)

        toks, state = client.stream(rid, on_token=on_token)
        assert state == State.CANCELLED.value
        assert 2 <= len(toks) < 64
        stats = client.stats()
        assert stats["cancelled"] == 1
        assert stats["pool"]["reserved_blocks"] == 0
    finally:
        server.shutdown()


def test_unread_streams_and_old_requests_are_garbage_collected(world):
    """A client that submits but never opens its stream (or drops the
    connection early) must not leak the stream queue or the Request
    forever: the dispatcher reaps terminal streams past
    ``stream_ttl_s`` and evicts the oldest finished requests beyond
    ``request_cap``."""
    import time as _time
    from repro.serving.server import CacheCraftServer, ServeClient
    cfg, params, kb = world
    eng = build_engine(_spec(), cfg=cfg, params=params, store=None)
    server = CacheCraftServer(eng, stream_ttl_s=0.0, request_cap=1)
    server.start()
    try:
        client = ServeClient(server.host, server.port)
        reqs = _requests(kb, n=2, max_new=3)
        rid_a = client.submit(reqs[0])     # stream never opened
        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline:
            if client.stats()["server"]["inflight"] == 0:
                break
            _time.sleep(0.05)
        assert client.stats()["server"]["inflight"] == 0

        rid_b = client.submit(reqs[1])     # its dispatches drive the GC
        toks, state = client.stream(rid_b)
        assert state == State.DONE.value and len(toks) == 3
        with server._lock:
            assert rid_a not in server._streams      # TTL reap
            assert rid_a not in server._done_at
            assert rid_a not in server._requests     # cap eviction
    finally:
        server.shutdown()


# ---- session-structured workloads -------------------------------------------
def test_sessions_have_independent_prefixes(world):
    _cfg, _params, kb = world
    reqs = generate(kb, WorkloadConfig(num_requests=16, qpm=1e9, seed=2,
                                       sessions=4))
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
    assert len(by_session) > 1
    # same session -> same prefix object content; different sessions ->
    # different prefixes (the old generator shared ONE array object)
    for sess, rs in by_session.items():
        for r in rs[1:]:
            np.testing.assert_array_equal(r.system_tokens,
                                          rs[0].system_tokens)
    prefixes = [tuple(rs[0].system_tokens.tolist())
                for rs in by_session.values()]
    assert len(set(prefixes)) == len(prefixes)


def test_multi_turn_history_grows_and_chunks_rotate(world):
    _cfg, _params, kb = world
    wl = WorkloadConfig(num_requests=24, qpm=1e9, seed=2, sessions=3,
                        turns=3, k_chunks=3, history_max=48)
    reqs = generate(kb, wl)
    later_turns = [r for r in reqs if r.turn > 0]
    assert later_turns, "trace produced no multi-turn continuation"
    for r in later_turns:
        # turn > 0 carries accumulated history in the prefix
        assert len(r.system_tokens) > wl.sys_len
        assert len(r.system_tokens) <= wl.sys_len + wl.history_max
    # rotation: a later turn sees the same chunk SET at different
    # positions at least once in the trace (same session qseed pool)
    rotated = False
    first = {}
    for r in reqs:
        key = (r.session,
               frozenset(tuple(c.tolist()) for c in r.chunk_tokens))
        order = [tuple(c.tolist()) for c in r.chunk_tokens]
        if key in first and first[key] != order:
            rotated = True
        first.setdefault(key, order)
    assert rotated


def test_generate_is_deterministic(world):
    _cfg, _params, kb = world
    wl = WorkloadConfig(num_requests=12, qpm=600, seed=4, sessions=3,
                        turns=2, tenants=(TenantSpec("a", 1.0, 5.0),
                                          TenantSpec("b", 1.0, 9.0)))
    a, b = generate(kb, wl), generate(kb, wl)
    for x, y in zip(a, b):
        assert x.arrival_time == y.arrival_time
        assert x.tenant == y.tenant and x.deadline_s == y.deadline_s
        np.testing.assert_array_equal(x.system_tokens, y.system_tokens)
        np.testing.assert_array_equal(x.question_tokens,
                                      y.question_tokens)


def test_tenants_assigned_per_session_with_slos(world):
    _cfg, _params, kb = world
    tenants = (TenantSpec("gold", 1.0, 2.5, max_new_tokens=3),
               TenantSpec("free", 1.0, 9.0))
    reqs = generate(kb, WorkloadConfig(num_requests=32, qpm=1e9, seed=8,
                                       sessions=8, tenants=tenants))
    assert {r.tenant for r in reqs} == {"gold", "free"}
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, set()).add(r.tenant)
    assert all(len(ts) == 1 for ts in by_session.values())
    for r in reqs:
        if r.tenant == "gold":
            assert r.deadline_s == 2.5 and r.max_new_tokens == 3
        else:
            assert r.deadline_s == 9.0


def test_single_turn_trace_preserves_legacy_stream(world):
    """Determinism contract: session structure must not consume the
    main arrival rng — a multi-turn config produces the SAME arrival
    times and session draws as the single-turn one."""
    _cfg, _params, kb = world
    a = generate(kb, WorkloadConfig(num_requests=10, qpm=600, seed=3))
    b = generate(kb, WorkloadConfig(num_requests=10, qpm=600, seed=3,
                                    turns=3))
    for x, y in zip(a, b):
        assert x.arrival_time == y.arrival_time
        assert x.session == y.session
