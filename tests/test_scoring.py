"""Property tests for the Cache-Craft reusability metrics (§3.1-§3.2)."""
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.core import scoring
from repro.core.focus import FocusTracker, predict_focused_chunks
from repro.core.select import select_recompute_tokens


def _mk_scores(prefix_hashes, prefix_inter, cci=0.7, length=10):
    return scoring.ChunkScores(
        chunk_index=len(prefix_hashes), length=length, a_bar=0.1, b_bar=0.1,
        cci=cci, prefix_hashes=list(prefix_hashes),
        prefix_inter=list(prefix_inter),
        token_inter=np.arange(length, dtype=np.float64))


# ---- beta (Eq. 6) -----------------------------------------------------------
@given(st.lists(st.floats(0.01, 10), min_size=1, max_size=6), st.data())
def test_beta_bounds_and_monotonicity(weights, data):
    hashes = [f"h{i}" for i in range(len(weights))]
    sc = _mk_scores(hashes, weights)
    keep = data.draw(st.sets(st.sampled_from(hashes)))
    b = scoring.beta_score(sc, sorted(keep))
    assert 0.0 <= b <= 1.0 + 1e-9
    # adding one more kept chunk never decreases beta
    missing = [h for h in hashes if h not in keep]
    if missing:
        b2 = scoring.beta_score(sc, sorted(keep | {missing[0]}))
        assert b2 >= b - 1e-12


def test_beta_full_and_empty():
    sc = _mk_scores(["a", "b"], [1.0, 3.0])
    assert scoring.beta_score(sc, ["a", "b"]) == pytest.approx(1.0)
    assert scoring.beta_score(sc, []) == pytest.approx(0.0)
    assert scoring.beta_score(sc, ["a"]) == pytest.approx(0.25)
    # chunk cached with no prefix is always fully reusable
    assert scoring.beta_score(_mk_scores([], []), ["x"]) == 1.0


# ---- gamma (Eq. 7, Kendall tau) --------------------------------------------
@given(st.permutations(list("abcdef")))
def test_gamma_identity_and_reversal(perm):
    order = list(perm)
    assert scoring.kendall_tau_distance(order, order) == 0.0
    assert scoring.kendall_tau_distance(order, order[::-1]) == \
        pytest.approx(1.0)


@given(st.lists(st.sampled_from("abcdefgh"), min_size=0, max_size=8,
                unique=True), st.data())
def test_gamma_matches_bruteforce(old, data):
    new = data.draw(st.permutations(old))
    g = scoring.kendall_tau_distance(old, list(new))
    common = [h for h in old if h in set(new)]
    m = len(common)
    if m < 2:
        assert g == 0.0
        return
    rank = {h: i for i, h in enumerate(new)}
    d = sum(1 for i in range(m) for j in range(i + 1, m)
            if rank[common[i]] > rank[common[j]])
    assert g == pytest.approx(d / (m * (m - 1) / 2))


def test_beta_prime_order_penalty():
    """Same chunk set, permuted order -> beta' < beta (paper's motivation
    for gamma: beta alone is order-invariant)."""
    sc = _mk_scores(["a", "b", "c"], [1.0, 1.0, 1.0])
    assert scoring.beta_prime(sc, ["a", "b", "c"]) == pytest.approx(1.0)
    assert scoring.beta_prime(sc, ["c", "b", "a"]) == pytest.approx(0.0)
    mid = scoring.beta_prime(sc, ["b", "a", "c"])
    assert 0.0 < mid < 1.0


# ---- CCI / CFO --------------------------------------------------------------
def test_cci_monotone_in_external_influence():
    inter = np.zeros((2, 4, 4))
    inter[:, 2, 2] = 10.0            # intra
    lengths = [4, 4, 4, 4]
    lo = scoring.chunk_scores(inter, lengths, 2, ["s", "a"], np.zeros(4))
    inter2 = inter.copy()
    inter2[:, 2, 0] = 50.0           # heavy external attention
    hi = scoring.chunk_scores(inter2, lengths, 2, ["s", "a"], np.zeros(4))
    assert hi.cci > lo.cci
    assert 0.5 <= hi.cci <= 1.0      # sigmoid of non-negative ratio


@given(st.floats(0.0, 1.0), st.floats(0.1, 4.0))
def test_cfo_clipped(cci, alpha):
    sc = _mk_scores(["a"], [1.0], cci=cci)
    c = scoring.cfo(sc, [], alpha=alpha)   # beta=0 -> cfo = alpha*cci
    assert 0.0 <= c <= 1.0
    assert c == pytest.approx(min(1.0, alpha * cci))


def test_inter_matrix_segment_sums():
    stats = np.zeros((2, 6, 4))
    q_chunk = np.array([0, 0, 1, 1, 2, 2])
    stats[:, 2, 0] = 1.5             # chunk1 row attends chunk0 keys
    stats[:, 3, 1] = 2.0
    m = scoring.inter_matrix(stats, q_chunk, 3)
    assert m[0, 1, 0] == pytest.approx(1.5)
    assert m[0, 1, 1] == pytest.approx(2.0)
    assert m[0, 0, 2] == 0.0


# ---- token selection (Eq. 14) ----------------------------------------------
@given(st.integers(1, 50), st.floats(0.0, 1.0))
def test_select_count(n, frac):
    ti = np.random.default_rng(0).normal(size=n)
    idx = select_recompute_tokens(ti, frac, "cachecraft")
    assert len(idx) == int(np.ceil(frac * n))
    assert (np.diff(idx) > 0).all()          # sorted, unique
    # selected tokens have the highest inter-attention
    if 0 < len(idx) < n:
        assert ti[idx].min() >= np.partition(ti, -len(idx))[-len(idx)] - 1e-9


def test_select_strategies():
    ti = np.array([5.0, 1.0, 4.0, 2.0, 3.0])
    tot = np.array([1.0, 5.0, 2.0, 4.0, 3.0])
    assert list(select_recompute_tokens(ti, 0.4, "cachecraft")) == [0, 2]
    assert list(select_recompute_tokens(ti, 0.4, "h2o",
                                        token_total=tot)) == [1, 3]
    assert len(select_recompute_tokens(
        ti, 0.4, "random", rng=np.random.default_rng(7))) == 2
    assert len(select_recompute_tokens(ti, 1.0, "none")) == 0
    assert len(select_recompute_tokens(ti, 0.1, "all")) == 5


def test_select_random_requires_rng():
    """The silent default_rng(0) fallback re-seeded identically per
    call, correlating the Random-Recomp baseline across chunks — now
    an rng must come from the plan level, and the old fixed seed is
    only available behind the explicit ``seeded_default`` kwarg."""
    ti = np.arange(10.0)
    with pytest.raises(ValueError, match="random"):
        select_recompute_tokens(ti, 0.4, "random")
    a = select_recompute_tokens(ti, 0.4, "random", seeded_default=True)
    b = select_recompute_tokens(ti, 0.4, "random", seeded_default=True)
    assert list(a) == list(b)               # explicit opt-in: deterministic
    rng = np.random.default_rng(3)
    draws = [select_recompute_tokens(ti, 0.4, "random", rng=rng)
             for _ in range(8)]
    assert len({tuple(d) for d in draws}) > 1   # plan-level rng advances


# ---- Algorithm 1 -------------------------------------------------------------
def test_focus_detects_dominant_chunks():
    L, k = 12, 5
    inter = np.ones((L, k)) * 0.1
    inter[:, 1] = 5.0
    inter[:, 3] = 4.0
    res = predict_focused_chunks(inter, w=3)
    assert res.converged
    assert {1, 3} <= res.focused
    assert 0 not in res.focused or len(res.focused) < k
    assert res.cutoff_layer < L - 1


def test_focus_tracker_incremental_matches_batch():
    rng = np.random.default_rng(3)
    inter = np.abs(rng.normal(size=(10, 4))) + \
        np.array([3.0, 0.1, 0.1, 2.0])
    batch = predict_focused_chunks(inter, w=3)
    tr = FocusTracker(4, w=3)
    for l in range(10):
        if tr.update(inter[l]):
            break
    assert tr.converged == batch.converged
    if tr.converged:
        assert tr.focused == batch.focused
        assert tr.cutoff_layer == batch.cutoff_layer


@given(st.integers(1, 8), st.integers(2, 20), st.integers(0, 1000))
def test_focus_always_terminates(k, layers, seed):
    rng = np.random.default_rng(seed)
    inter = np.abs(rng.normal(size=(layers, k)))
    res = predict_focused_chunks(inter, w=3)
    assert 1 <= len(res.focused) <= k
    assert 0 <= res.cutoff_layer <= layers - 1
