"""Cache-Craft executor integration: planning, reuse quality ordering,
focus early termination, variant management, ablation flags."""
import jax
import numpy as np
import pytest

from repro.configs import get_tiny
from repro.core.chunkstore import ChunkStore
from repro.core.prefill import CacheCraftExecutor
from repro.core.tiers import TieredStore
from repro.models import model as M
from repro.serving.metrics import ServingCounters, relative_deviation


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    cfg = get_tiny("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    V = cfg.vocab_size
    kb = [rng.integers(0, V, 24) for _ in range(8)]
    sys_t = rng.integers(0, V, 8)
    q1 = rng.integers(0, V, 12)
    q2 = rng.integers(0, V, 12)
    return cfg, params, kb, sys_t, q1, q2, tmp_path_factory


def _store(world, tag):
    cfg, params, kb, sys_t, q1, q2, tmp = world
    tiers = TieredStore(1 << 30, 1 << 30,
                        str(tmp.mktemp(tag)), start_worker=False)
    return ChunkStore(tiers, n_chunks=20, m_variants=3)


def test_warmup_then_hits(world):
    cfg, params, kb, sys_t, q1, q2, _ = world
    store = _store(world, "warm")
    ex = CacheCraftExecutor(cfg, params, store, use_focus=False)
    r0 = ex.process(sys_t, kb[:3], q1)
    assert r0.compute_fraction == pytest.approx(1.0)
    assert store.num_variants() == 4            # sys + 3 chunks
    r1 = ex.process(sys_t, [kb[1], kb[0], kb[3]], q2)
    assert sum(d.is_hit for d in r1.plan.decisions) == 3
    assert r1.compute_fraction < 1.0
    assert r1.plan.recompute_fraction < 1.0


def test_forced_full_recompute_is_exact(world):
    cfg, params, kb, sys_t, q1, q2, _ = world
    store = _store(world, "exact")
    CacheCraftExecutor(cfg, params, store, use_focus=False).process(
        sys_t, kb[:3], q1)
    oracle = CacheCraftExecutor(cfg, params, None, strategy="all",
                                use_focus=False)
    ro = oracle.process(sys_t, [kb[1], kb[0], kb[3]], q2)
    exf = CacheCraftExecutor(cfg, params, store, use_focus=False,
                             force_recompute_fraction=1.0,
                             store_fixed_variants=False)
    rf = exf.process(sys_t, [kb[1], kb[0], kb[3]], q2)
    np.testing.assert_allclose(rf.logits_last, ro.logits_last,
                               rtol=3e-4, atol=3e-4)


def test_quality_improves_with_recompute(world):
    cfg, params, kb, sys_t, q1, q2, _ = world
    store = _store(world, "qual")
    CacheCraftExecutor(cfg, params, store, use_focus=False).process(
        sys_t, kb[:3], q1)
    oracle = CacheCraftExecutor(cfg, params, None, strategy="all",
                                use_focus=False)
    ro = oracle.process(sys_t, [kb[1], kb[0], kb[3]], q2)
    devs = {}
    for frac in (0.0, 0.3, 0.7):
        ex = CacheCraftExecutor(cfg, params, store, use_focus=False,
                                force_recompute_fraction=frac,
                                store_fixed_variants=False,
                                store_new_chunks=False)
        r = ex.process(sys_t, [kb[1], kb[0], kb[3]], q2)
        devs[frac] = relative_deviation(r.logits_last, ro.logits_last)
    assert devs[0.7] < devs[0.0]
    assert devs[0.3] <= devs[0.0] + 1e-6


def test_focus_early_termination_reduces_compute(world):
    cfg, params, kb, sys_t, q1, q2, _ = world
    store = _store(world, "focus")
    CacheCraftExecutor(cfg, params, store, use_focus=False).process(
        sys_t, kb[:4], q1)
    no_focus = CacheCraftExecutor(cfg, params, store, use_focus=False,
                                  force_recompute_fraction=0.5,
                                  store_fixed_variants=False,
                                  store_new_chunks=False)
    with_focus = CacheCraftExecutor(cfg, params, store, use_focus=True,
                                    focus_w=2,
                                    force_recompute_fraction=0.5,
                                    store_fixed_variants=False,
                                    store_new_chunks=False)
    rn = no_focus.process(sys_t, kb[:4], q2)
    rf = with_focus.process(sys_t, kb[:4], q2)
    if rf.focus_cutoff is not None and rf.focused is not None and \
            len(rf.focused) < 4:
        assert rf.active_rows_layers < rn.active_rows_layers


def test_prefill_rows_counted_after_focus_rebucket(world, monkeypatch):
    """A request whose focus drops rows re-buckets its later windows to
    fewer rows: those windows count the smaller bucket they ran. The
    tracker here focuses on the first chunk after one layer (random
    weights spread attention too evenly for Algorithm 1 to drop any)."""
    import repro.core.prefill as P
    from repro.core.focus import FocusTracker

    class FirstChunk(FocusTracker):
        def update(self, inter_layer):
            self.converged, self.focused, self.cutoff_layer = True, {1}, 0
            return True
    monkeypatch.setattr(P, "FocusTracker", FirstChunk)
    cfg, params, kb, sys_t, q1, q2, _ = world
    store = _store(world, "rows")
    CacheCraftExecutor(cfg, params, store, use_focus=False).process(
        sys_t, kb[:4], q1)
    c = ServingCounters()
    ex = CacheCraftExecutor(cfg, params, store, use_focus=True, focus_w=1,
                            force_recompute_fraction=1.0,
                            store_fixed_variants=False,
                            store_new_chunks=False, counters=c)
    r = ex.process(sys_t, kb[:4], q2)
    L, b = cfg.num_layers, ex.bucket
    n = r.plan.num_active_tokens                # 8 + 4 x 24 + 12 = 116
    kept = n - 3 * 24                           # chunks 2-4 dropped
    assert r.focused == {1} and n == 116
    assert c.prefill_rows_active == r.active_rows_layers == \
        n + kept * (L - 1)
    assert c.prefill_rows_run == -(-n // b) * b + \
        -(-kept // b) * b * (L - 1)


def test_ablation_flags_change_output(world):
    """Table 3: disabling the RPE fix or the causality fix must degrade
    the reuse path (different, worse logits than the fixed version)."""
    cfg, params, kb, sys_t, q1, q2, _ = world
    store = _store(world, "abl")
    CacheCraftExecutor(cfg, params, store, use_focus=False).process(
        sys_t, kb[:3], q1)
    oracle = CacheCraftExecutor(cfg, params, None, strategy="all",
                                use_focus=False)
    ro = oracle.process(sys_t, [kb[1], kb[2], kb[0]], q2)
    outs = {}
    for name, kw in {
        "fixed": dict(fix_rpe=True, fix_causality=True),
        "no_rpe": dict(fix_rpe=False, fix_causality=True),
        "no_causal": dict(fix_rpe=True, fix_causality=False),
    }.items():
        ex = CacheCraftExecutor(cfg, params, store, strategy="none",
                                use_focus=False,
                                store_fixed_variants=False,
                                store_new_chunks=False, **kw)
        r = ex.process(sys_t, [kb[1], kb[2], kb[0]], q2)
        outs[name] = relative_deviation(r.logits_last, ro.logits_last)
    assert outs["no_rpe"] > outs["fixed"]


def test_inapplicable_arch_raises():
    cfg = get_tiny("mamba2-370m")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="inapplicable"):
        CacheCraftExecutor(cfg, params, store="not-none")  # type: ignore
