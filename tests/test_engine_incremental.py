"""Incremental decode batch vs full rebuild, and reserve-at-admission.

Tier-1 gates for the reservation + incremental-decode tentpole:

* a churny join/leave schedule stepped with the incremental decode
  batch must produce per-step decode logits and final pool KV identical
  to the always-rebuild path, while handling membership changes without
  full rebuilds (asserted via the rebuild counter);
* under pool pressure with reservations on, no request may ever enter
  the packed compute pass and then fail ``write_prefill``
  (``burn_requeues == 0``);
* a churny pool-starved schedule stepped with reservation-aware
  preemption must produce, for every request — the preempted ones
  included — the same output tokens, and final decode logits and final
  pool KV within 1e-5 of an unpressured (large-pool) run of the same
  workload.
"""
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


def _churny_requests(kb):
    """All-at-once arrivals with varied decode lengths: with one
    admission per iteration the decode batch sees a join or a leave on
    most steps."""
    wl = WorkloadConfig(num_requests=6, qpm=1e9, seed=11, k_chunks=3,
                        max_new_tokens=4)
    reqs = generate(kb, wl)
    for r, n in zip(reqs, (3, 5, 7, 9, 4, 6)):
        r.max_new_tokens = n
    return reqs


def _run(cfg, params, kb, incremental):
    eng = build_engine(
        EngineSpec(strategy="all", use_focus=False,
                   pool_blocks=512, decode_bucket_b=4, seq_bucket=320,
                   sched=SchedulerConfig(max_batch_tokens=100_000,
                                         max_decode_batch=4,
                                         max_prefill_batch=1),
                   incremental_decode=incremental, trace_decode=True),
        cfg=cfg, params=params, store=None)
    reqs = _churny_requests(kb)
    stats = eng.run(reqs)
    return eng, stats, reqs


def test_incremental_matches_rebuild(world):
    cfg, params, kb = world
    eng_i, stats_i, reqs_i = _run(cfg, params, kb, incremental=True)
    eng_r, stats_r, reqs_r = _run(cfg, params, kb, incremental=False)

    assert stats_i.completed == 6 and stats_i.failed == 0
    assert stats_r.completed == 6 and stats_r.failed == 0

    # membership churn was handled in place, not by rebuilding: the
    # incremental engine rebuilt only to create the batch, the rebuild
    # engine regathered on every join/leave
    ci, cr = eng_i.counters, eng_r.counters
    assert ci.decode_rebuilds == 1
    assert cr.decode_rebuilds > ci.decode_rebuilds
    assert ci.decode_joins >= 4            # joins absorbed without rebuild
    assert ci.decode_leaves >= 5           # leaves masked the row in place
    assert ci.decode_rows_recycled >= 1    # masked rows were reused
    assert cr.decode_joins == 0 and cr.decode_leaves == 0

    # identical decode trajectory: same number of steps, and per-step
    # logits bit-identical for every live request
    assert stats_i.decode_steps == stats_r.decode_steps
    assert len(eng_i.decode_trace) == len(eng_r.decode_trace)
    for step, (ti, tr) in enumerate(zip(eng_i.decode_trace,
                                        eng_r.decode_trace)):
        assert set(ti) == set(tr), f"step {step}: batch membership differs"
        for rid in ti:
            np.testing.assert_array_equal(
                ti[rid], tr[rid],
                err_msg=f"step {step}, rid {rid}: decode logits differ")

    # identical final pool KV per request (gathered before free_table)
    assert set(eng_i.final_kv) == set(eng_r.final_kv)
    for rid in eng_i.final_kv:
        ki, vi, pi = eng_i.final_kv[rid]
        kr, vr, pr = eng_r.final_kv[rid]
        np.testing.assert_array_equal(pi, pr)
        np.testing.assert_array_equal(ki, kr)
        np.testing.assert_array_equal(vi, vr)

    # and identical outputs, of course
    for ri, rr in zip(reqs_i, reqs_r):
        assert ri.state == State.DONE
        assert ri.output_tokens == rr.output_tokens


def test_zero_burn_requeues_under_pool_pressure(world):
    """Reserve-at-admission: with a pool that holds ~1.5 requests, every
    admission must already own its blocks — no request may burn packed
    compute and then fail the KV write-back."""
    cfg, params, kb = world
    eng = build_engine(
        EngineSpec(strategy="all", use_focus=False,
                   pool_blocks=12,          # ~192 tokens: one request
                   sched=SchedulerConfig(max_batch_tokens=100_000,
                                         max_decode_batch=8,
                                         max_prefill_batch=4)),
        cfg=cfg, params=params, store=None)
    wl = WorkloadConfig(num_requests=4, qpm=1e9, seed=3, k_chunks=3,
                        max_new_tokens=3)
    reqs = generate(kb, wl)
    stats = eng.run(reqs)
    c = eng.counters
    assert c.burn_requeues == 0            # the burn path is gone
    assert c.reserve_failures > 0          # pressure was actually exerted
    assert stats.completed == 4 and stats.failed == 0
    assert all(r.state == State.DONE for r in reqs)
    # reservations fully settled, pool drained back to empty
    assert c.reservations_made == c.reservations_committed \
        + c.reservations_cancelled
    assert eng.pool.reserved_blocks == 0 and eng.pool.live_blocks == 0
    assert eng.pool.free_blocks == eng.pool.num_blocks


def _preempt_churn_requests(kb):
    """Two long decodes hog the pool, four short requests churn the
    decode batch behind them — every short admission follows a
    preemption or a completion, so joins/leaves interleave with
    preemption teardowns."""
    wl = WorkloadConfig(num_requests=6, qpm=1e9, seed=17, k_chunks=3,
                        max_new_tokens=4)
    reqs = generate(kb, wl)
    for r, n in zip(reqs, (18, 18, 3, 5, 4, 6)):
        r.max_new_tokens = n
    return reqs


def _run_preempt(cfg, params, kb, pool_blocks, preempt_after):
    eng = build_engine(
        EngineSpec(strategy="all", use_focus=False,
                   pool_blocks=pool_blocks, decode_bucket_b=4,
                   seq_bucket=512,
                   sched=SchedulerConfig(
                       max_batch_tokens=100_000,
                       max_decode_batch=4,
                       max_prefill_batch=2,
                       preempt_after_iters=preempt_after),
                   trace_decode=True),
        cfg=cfg, params=params, store=None)
    reqs = _preempt_churn_requests(kb)
    stats = eng.run(reqs)
    last = {}
    for step_logits in eng.decode_trace:
        last.update(step_logits)
    return eng, stats, reqs, last


def test_preempted_requests_bit_identical_to_unpressured(world):
    """A preempted request re-prefills from scratch and re-decodes; its
    output tokens must equal, and its final logits and final pool KV
    lie within 1e-5 of, an unpressured run where it was never
    preempted.

    Not bitwise: a preempted request re-enters prefill packed with
    different neighbours (or alone), so its prefill window runs at a
    different packed shape, and XLA picks its reduction blocking per
    shape. The KV it writes can then differ in the last bit (about
    1e-6 on the tiny preset), and so can every logit decoded from it."""
    cfg, params, kb = world
    eng_u, stats_u, reqs_u, last_u = _run_preempt(
        cfg, params, kb, pool_blocks=512, preempt_after=0)
    eng_p, stats_p, reqs_p, last_p = _run_preempt(
        cfg, params, kb, pool_blocks=20, preempt_after=4)

    assert eng_u.counters.preemptions == 0
    assert eng_p.counters.preemptions > 0      # pressure preempted
    assert stats_u.failed == 0 and stats_p.failed == 0
    assert stats_u.completed == 6 and stats_p.completed == 6
    assert all(r.state == State.DONE for r in reqs_p)

    # outputs equal, final decode logits within the bound per request
    for ru, rp in zip(reqs_u, reqs_p):
        assert ru.output_tokens == rp.output_tokens, \
            f"rid {ru.rid}: outputs diverged under preemption"
    assert set(last_u) == set(last_p)
    for rid in last_u:
        np.testing.assert_allclose(
            last_u[rid], last_p[rid], rtol=1e-5, atol=1e-5,
            err_msg=f"rid {rid}: final decode logits differ")

    # final pool KV (gathered before free_table): positions exact,
    # K/V within the bound
    assert set(eng_u.final_kv) == set(eng_p.final_kv)
    for rid in eng_u.final_kv:
        ku, vu, pu = eng_u.final_kv[rid]
        kp, vp, pp = eng_p.final_kv[rid]
        np.testing.assert_array_equal(pu, pp)
        np.testing.assert_allclose(ku, kp, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(vu, vp, rtol=1e-5, atol=1e-5)

    # preemption churned the decode batch in place where it could
    cp = eng_p.counters
    assert cp.decode_leaves > 0
    assert cp.burn_requeues == 0
    # pool fully settled after the pressured run
    assert eng_p.pool.reserved_blocks == 0
    assert eng_p.pool.live_blocks == 0
    assert eng_p.pool.free_blocks == eng_p.pool.num_blocks


def test_decode_batch_shape_growth_triggers_rebuild(world):
    """A joiner that does not fit the row arena (S too small) must fall
    back to a full rebuild rather than truncate its KV."""
    cfg, params, kb = world
    eng = build_engine(
        EngineSpec(strategy="all", use_focus=False,
                   pool_blocks=512, decode_bucket_b=4, seq_bucket=32,
                   sched=SchedulerConfig(max_batch_tokens=100_000,
                                         max_decode_batch=4,
                                         max_prefill_batch=1)),
        cfg=cfg, params=params, store=None)
    wl = WorkloadConfig(num_requests=3, qpm=1e9, seed=6, k_chunks=2,
                        max_new_tokens=3)
    reqs = generate(kb, wl)
    # second request much longer than the first: S must grow
    reqs[1].question_tokens = np.concatenate(
        [reqs[1].question_tokens,
         np.zeros(64, reqs[1].question_tokens.dtype)])
    stats = eng.run(reqs)
    assert stats.completed == 3 and stats.failed == 0
    assert eng.counters.decode_rebuilds >= 2
    for r in reqs:
        assert len(r.output_tokens) == r.max_new_tokens
