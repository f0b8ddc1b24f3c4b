"""Attention-backend equivalence suite: one registry, interchangeable
implementations (the ISSUE 6 tentpole gates).

* registry contract — every published name dispatches, unknown names
  raise, ``sharded`` degrades to dense when no serving mesh is
  installed (single-device processes must keep working)
* packed == sequential per backend: the equivalence gate that keeps
  chunk-cache reuse honest, run through the real executor
* segment-mask edge case — perturbing one packed request must not move
  another's logits by a single bit (no cross-segment attention leak)
* decode-slot edge case — masked batch rows (positions == -1) stay
  inert and finite while the live row's logits match a 1-row decode
* sharded — subprocess with 4 fake host devices: engine tokens equal
  and logits within 1e-5 of single-device while per-device KV bytes
  and attention FLOPs are strictly lower; head-indivisible meshes
  rejected

Kernel (Pallas interpret-mode) cases carry the ``kernel_interpret``
marker: included in default local runs, split into their own required
CI job, deselected from the tier1 lane.
"""
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny
from repro.core.prefill import CacheCraftExecutor, pack_cache
from repro.models import backend as AB
from repro.models import model as M

KERNEL = pytest.mark.kernel_interpret
# 'sharded' runs here too: without a serving mesh it must fall back to
# dense (the single-device degradation half of its contract)
BACKENDS = ["dense", pytest.param("kernel", marks=KERNEL), "sharded"]


@pytest.fixture(scope="module")
def world():
    cfg = get_tiny("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    V = cfg.vocab_size
    kb = [rng.integers(0, V, 24) for _ in range(4)]
    sys_a = rng.integers(0, V, 8)
    sys_b = rng.integers(0, V, 8)
    q1 = rng.integers(0, V, 12)
    q2 = rng.integers(0, V, 12)
    return cfg, params, kb, sys_a, sys_b, q1, q2


@pytest.fixture(scope="module")
def prefilled(world):
    """One dense prefill shared by the decode-edge tests: its packed KV
    arena + the greedy next token."""
    cfg, params, kb, sys_a, _, q1, _ = world
    ex = CacheCraftExecutor(cfg, params, None, use_focus=False,
                            attn_impl="dense")
    res = ex.process(sys_a, kb[:2], q1)
    cache = pack_cache(cfg, res.k_layers, res.v_layers, res.pos_layout)
    tok = int(np.argmax(res.logits_last[:cfg.vocab_size]))
    return cfg, params, res, cache, tok


# ---- registry contract ------------------------------------------------------
def test_registry_contract(world):
    cfg = world[0]
    assert {"auto", "dense", "kernel", "sharded", "flash",
            "flash_skip", "flash_cp"} <= set(AB.BACKENDS)
    with pytest.raises(ValueError, match="unknown attn_impl"):
        AB.attend(SimpleNamespace(attn_impl="nope", cfg=cfg), "global",
                  None, None, None, None)


def test_serving_rules_reject_indivisible_heads():
    from repro.distributed import sharding as SH
    cfg = get_tiny("llama3-8b").replace(num_heads=4, num_kv_heads=4)

    class FakeMesh:
        axis_names = ("heads",)
        shape = {"heads": 3}

    with pytest.raises(ValueError):
        SH.serving_rules(FakeMesh(), cfg)
    with pytest.raises(ValueError):
        SH.serving_kv_shards(FakeMesh(), cfg)


# ---- packed == sequential per backend ---------------------------------------
@pytest.mark.parametrize("impl", BACKENDS)
def test_packed_matches_sequential(world, impl):
    cfg, params, kb, sys_a, sys_b, q1, q2 = world
    AB.set_serving_mesh(None)          # sharded -> dense fallback here
    r1 = (sys_a, kb[:2], q1)
    r2 = (sys_b, kb[2:4], q2)
    ex = CacheCraftExecutor(cfg, params, None, use_focus=False,
                            attn_impl=impl)
    res_seq = [ex.process(*r1), ex.process(*r2)]
    res_pkd = ex.process_batch([r1, r2])
    for rs, rp in zip(res_seq, res_pkd):
        assert rp.total_len == rs.total_len
        np.testing.assert_allclose(rp.logits_last, rs.logits_last,
                                   rtol=2e-4, atol=2e-4)


def test_packed_segment_isolation(world):
    """Segment-mask edge case: request 0's packed logits must be
    bit-identical whether request 1 carries q2 or a same-length
    perturbation of it — any drift means attention leaked across the
    segment mask."""
    cfg, params, kb, sys_a, sys_b, q1, q2 = world
    ex = CacheCraftExecutor(cfg, params, None, use_focus=False,
                            attn_impl="dense")
    base = ex.process_batch([(sys_a, kb[:2], q1), (sys_b, kb[2:4], q2)])
    q2p = (np.asarray(q2) + 1) % cfg.vocab_size
    pert = ex.process_batch([(sys_a, kb[:2], q1), (sys_b, kb[2:4], q2p)])
    assert np.array_equal(np.asarray(base[0].logits_last),
                          np.asarray(pert[0].logits_last))
    # sanity: the perturbation itself was visible to request 1
    assert not np.array_equal(np.asarray(base[1].logits_last),
                              np.asarray(pert[1].logits_last))


def _decode(cfg, impl):
    """One jitted decode step on a contiguous cache through backend
    ``impl``: (params, tokens, positions, cache, slots) -> logits."""
    @jax.jit
    def step(params, tokens, positions, cache, slots):
        return M.decode_step(cfg, params, tokens, positions, cache,
                             decode_slot=slots, attn_impl=impl).logits
    return step


# ---- kernel backend: cross-impl agreement -----------------------------------
@KERNEL
def test_kernel_matches_dense_prefill_and_decode(world, prefilled):
    cfg, params, kb, sys_a, _, q1, _ = world
    _, _, res_d, cache_d, tok = prefilled
    ex_k = CacheCraftExecutor(cfg, params, None, use_focus=False,
                              attn_impl="kernel")
    res_k = ex_k.process(sys_a, kb[:2], q1)
    np.testing.assert_allclose(res_k.logits_last, res_d.logits_last,
                               rtol=2e-4, atol=2e-4)
    # one decode step via the Pallas decode kernel vs dense
    cache_k = pack_cache(cfg, res_k.k_layers, res_k.v_layers,
                         res_k.pos_layout)
    toks = np.array([tok], np.int32)
    poss = np.array([res_d.total_len - 1], np.int32)
    lk = _decode(cfg, "kernel")(params, toks, poss, cache_k, poss)
    ld = _decode(cfg, "dense")(params, toks, poss, cache_d, poss)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)


# ---- decode-slot edge case: masked rows -------------------------------------
def _tile2(cache):
    """B=1 model cache -> B=2 (groups batch axis 1, tail batch axis 0)."""
    g = [{n: jnp.concatenate([e[n], e[n]], axis=1) for n in e}
         for e in cache["groups"]]
    t = [{n: jnp.concatenate([e[n], e[n]], axis=0) for n in e}
         for e in cache["tail"]]
    return {"groups": g, "tail": t}


@pytest.mark.parametrize("impl",
                         ["dense", pytest.param("kernel", marks=KERNEL)])
def test_decode_masked_row_inert(prefilled, impl):
    """A batch row with positions == slots == -1 (a decode batch
    hole) must not perturb the live row and must stay finite."""
    cfg, params, res, cache, tok = prefilled
    fn = _decode(cfg, impl)
    p = res.total_len - 1
    toks1 = np.array([tok], np.int32)
    pos1 = np.array([p], np.int32)
    ref = fn(params, toks1, pos1, cache, pos1)
    toks2 = np.array([tok, tok], np.int32)
    pos2 = np.array([p, -1], np.int32)
    lg = fn(params, toks2, pos2, _tile2(cache), pos2)
    lg, ref = np.asarray(lg), np.asarray(ref)
    assert np.isfinite(lg).all()       # masked row: garbage but finite
    np.testing.assert_allclose(lg[0], ref[0], rtol=2e-4, atol=2e-4)


# ---- sharded backend: subprocess on a forced 4-device host mesh -------------
def _run(code: str, timeout=900):
    return subprocess.run([sys.executable, "-c", code], cwd=os.getcwd(),
                          capture_output=True, text=True, timeout=timeout)


def test_sharded_engine_bit_identical_and_cheaper():
    """End-to-end engine run, unsharded vs head-sharded over 4 fake
    devices: identical output tokens, traced decode logits within 1e-5,
    and strictly lower per-device KV bytes + attention FLOPs (the
    tensor-parallel conservation gate).

    Logits are compared within a bound, not bitwise: each shard runs
    the attention einsums over H/4 heads, and XLA picks its reduction
    blocking per operand shape, so a per-head dot can round differently
    from the same head inside the full-width einsum (about 1e-6 on the
    tiny preset's O(1) logits). The per-head math is still the same
    math; only the summation order moves."""
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
assert e2.kv_shards == 4 and e1.kv_shards == 1
for a, b in zip(r1, r2):
    assert a.output_tokens == b.output_tokens, (a.output_tokens,
                                                b.output_tokens)
assert len(e1.decode_trace) == len(e2.decode_trace) > 0
for da, db in zip(e1.decode_trace, e2.decode_trace):
    assert set(da) == set(db)
    for rid in da:
        np.testing.assert_allclose(da[rid], db[rid], rtol=1e-5, atol=1e-5,
                                   err_msg=str(rid))
b1 = e1.pool.peak_kv_bytes_per_device()
b4 = e2.pool.peak_kv_bytes_per_device()
f1 = e1.counters.attn_flops_device
f4 = e2.counters.attn_flops_device
assert 0 < b4 < b1, (b4, b1)
assert 0 < f4 < f1, (f4, f1)
assert e1.counters.attn_flops_total == e2.counters.attn_flops_total
print("SHARDED_EQ_OK", b1, b4, f1, f4)
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_EQ_OK" in r.stdout
