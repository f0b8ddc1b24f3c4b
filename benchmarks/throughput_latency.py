"""Fig. 22: throughput and end-to-end latency under continuous batching
(ORCA-style) across load levels: Cache-Craft (0% and 30% recompute) vs
Prefix-Cache vs Full-Recomp. Engine clock = measured jitted compute +
modeled (unhidden) tier-load time.

Also emitted:

* ``fig22_admission_{serial,packed}`` — packed multi-request prefill vs
  serial admission under queue pressure (CI perf smoke asserts
  packed >= serial via ``--ci-smoke``).
* ``fig22_shared_blocks_{copy,zerocopy}`` — per-request KV copies vs
  zero-copy shared chunk blocks + delta-only admission on an
  overlapping-chunk workload (zero-copy tentpole).
* ``fig22_preemption_{off,on}`` — a pool-starved workload with
  reservation-aware preemption off vs on (preemption tentpole):
  preemption-on must complete every request with preemptions > 0, zero
  FAILED states, final decode logits bit-identical to an unpressured
  (large-pool) run, and a bounded head-of-line wait tail. The *gated*
  bound is the max head-stall iteration count (count-based, strictly
  lower than preemption-off); the p99 queue-head wait is emitted and
  recorded alongside (``p99_wait_lower`` in the gate JSON) but not
  gated, because it is wall-clock-derived and noisy on shared
  runners. Each run appends its numbers to
  ``results/BENCH_preemption.json`` so the bench trajectory records
  across sessions.
* ``fig22_sharded_{1dev,4dev}`` — the same workload served unsharded
  and head-sharded over 4 forced host devices (subprocess; the sharded
  attention-backend tentpole): per-device KV bytes and analytic
  attention FLOPs, with output tokens identical and traced decode
  logits bit-identical across the two runs. Trajectory appends to
  ``results/BENCH_sharded.json``.

``--ci-smoke`` runs the perf gates (admission throughput,
copy-vs-zerocopy reserved *blocks*, preemption
*counts* + logits bit-equality, eviction tier-miss *counts* (LRU vs
reuse-aware, from ``benchmarks.preloading.eviction_compare``), the
eager-vs-layerwise preload comparison (hidden/blocked layer counts +
measured exposed load), the sharded lane (bit-equality + strictly
fewer per-device KV bytes/attention FLOPs), and the quant lane
(quantized-tier deep-miss *counts* at an equal byte budget from
``eviction_quant_compare`` + the ROUGE delta-vs-fp32 quality gate from
``quant_quality_compare``, trajectory in ``results/BENCH_quant.json``),
and the serve lane (``benchmarks.serve_bench``: the online HTTP front
end streams every token bit-identical to an offline ``Engine.run``
replay of the same multi-turn mixed-tenant trace, survives a
mid-decode HTTP cancel with the pool settled, and reports per-tenant
p99 rollups; trajectory in ``results/BENCH_serve.json``) — all but the
first count-based, immune to shared-runner timing noise) and writes
the gate numbers to ``results/fig22_ci_smoke.json`` for the CI
artifact upload.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from benchmarks.common import emit, fresh_store, get_trained_model, \
    make_engine, make_world, record_trajectory as _record_trajectory
from repro.serving.engine import EngineStats
from repro.serving.metrics import queue_wait_p99, ttft_p99
from repro.serving.request import Request, State
from repro.serving.scheduler import SchedulerConfig
from repro.serving.workload import WorkloadConfig, generate

METHODS = {
    "full": dict(strategy="all", use_focus=False),
    "prefix": dict(strategy="prefix", use_focus=False),
    "cachecraft00": dict(strategy="none", use_focus=False),
    "cachecraft30": dict(strategy="cachecraft", use_focus=False,
                         force_recompute_fraction=0.3),
}


def _measure(cfg, params, store, sched, exkw, kb, n_req, qpm,
             warm_same: bool = False, workload_fn=None, **engine_kw):
    eng = make_engine(cfg, params, store, sched=sched, pool_blocks=4096,
                      store_fixed_variants=False, **exkw, **engine_kw)

    def make():
        if workload_fn is not None:
            return workload_fn()
        return generate(kb, WorkloadConfig(num_requests=n_req, qpm=qpm,
                                           seed=3, max_new_tokens=8))

    reqs = make()
    # warm the jit caches AND the chunk store before timing. For the
    # admission study the warm-up replays the measured workload twice
    # (fresh Request objects) so every packed-admission jit shape
    # (R, bucketed totals, block maps) and the steady-state chunk store
    # exist before the clock starts — run-twice-measure-second.
    if warm_same:
        eng.run(make())
        eng.run(make())
    else:
        eng.run(generate(kb, WorkloadConfig(num_requests=6, qpm=1e9,
                                            seed=7, max_new_tokens=8)))
    eng.clock = 0.0
    eng.stats = EngineStats()           # warm-up must not pollute counters
    eng.counters.reset()
    for r in reqs:
        r.t_enqueued = None
    stats = eng.run(reqs)
    done = [r for r in reqs if r.e2e_latency is not None]
    thr = len(done) / max(1e-9, stats.clock)
    lat = np.mean([r.e2e_latency for r in done])
    ttft = np.mean([r.ttft for r in done])
    return eng, stats, thr, lat, ttft


def _admission_compare(cfg, params, kb, n_req):
    """Packed vs single prefill admission under queue pressure (all
    requests arrive at once): packed multi-request prefill should beat
    the serial-admission baseline on throughput."""
    thr_by_label = {}
    for label, npack in (("serial", 1), ("packed", 4)):
        sched = SchedulerConfig(max_batch_tokens=8192, max_decode_batch=8,
                                max_prefill_batch=npack)
        exkw = dict(strategy="cachecraft", use_focus=False,
                    force_recompute_fraction=0.3)
        _eng, stats, thr, lat, ttft = _measure(
            cfg, params, fresh_store(f"tl-adm-{label}"), sched, exkw,
            kb, n_req, qpm=1e9, warm_same=True)
        emit(f"fig22_admission_{label}", lat * 1e6,
             f"throughput_rps={thr:.3f};mean_e2e_s={lat:.3f};"
             f"mean_ttft_s={ttft:.3f};"
             f"max_packed={stats.prefill_batch_max};"
             f"prefill_batches={stats.prefill_batches}")
        thr_by_label[label] = thr
    return thr_by_label


def _overlap_workload(kb, n_req, k=3, max_new=6):
    """Every request carries the SAME system prompt and chunk list
    (distinct questions), all arriving at once: the adversarial-best
    case for zero-copy sharing — N concurrent readers of the same hot
    chunks."""
    rng = np.random.default_rng(21)
    sys_t = rng.integers(0, kb.vocab_size, 8).astype(np.int32)
    chunks = [kb.chunks[i % len(kb.chunks)] for i in range(k)]
    return [Request(rid=i, system_tokens=sys_t,
                    chunk_tokens=[c.copy() for c in chunks],
                    question_tokens=rng.integers(
                        0, kb.vocab_size, 12).astype(np.int32),
                    max_new_tokens=max_new, arrival_time=0.0)
            for i in range(n_req)]


def _shared_blocks_compare(cfg, params, kb, n_req):
    """Per-request KV copies vs zero-copy shared chunk blocks on the
    overlapping workload. Returns the per-mode counters the CI gate
    checks: blocks reserved at admission (strictly fewer with delta
    reservation), live-block peak (the HBM saving), shared-block peak
    (refcount > 1 existed)."""
    out = {}
    for label, share in (("copy", False), ("zerocopy", True)):
        sched = SchedulerConfig(max_batch_tokens=100_000,
                                max_decode_batch=8, max_prefill_batch=4)
        exkw = dict(strategy="cachecraft", use_focus=False,
                    force_recompute_fraction=0.25)
        eng, stats, thr, lat, _ttft = _measure(
            cfg, params, fresh_store(f"tl-shb-{label}"), sched, exkw,
            kb, n_req, qpm=1e9, warm_same=True,
            workload_fn=lambda: _overlap_workload(kb, n_req),
            share_chunk_kv=share)
        c = eng.counters
        emit(f"fig22_shared_blocks_{label}", lat * 1e6,
             f"throughput_rps={thr:.3f};mean_e2e_s={lat:.3f};"
             f"blocks_reserved_total={c.blocks_reserved_total};"
             f"live_blocks_peak={c.live_blocks_peak};"
             f"shared_blocks_peak={c.shared_blocks_peak};"
             f"delta_blocks_saved={c.delta_blocks_saved};"
             f"cow_clones={c.cow_clones}")
        out[label] = dict(blocks_reserved_total=c.blocks_reserved_total,
                          live_blocks_peak=c.live_blocks_peak,
                          shared_blocks_peak=c.shared_blocks_peak,
                          delta_blocks_saved=c.delta_blocks_saved,
                          throughput_rps=thr)
    return out


def _starved_workload(kb, n_req, n_long=2, long_new=24, short_new=4):
    """The classic TTFT-tail regime: ``n_long`` long-decode requests
    arrive first and fill the whole pool (it is sized for ~2 requests);
    the short requests behind them stall on reservation for the length
    of a full decode drain unless the engine preempts. All-at-once
    arrivals keep admission order deterministic."""
    wl = WorkloadConfig(num_requests=n_req, qpm=1e9, seed=13, k_chunks=3,
                        max_new_tokens=short_new)
    reqs = generate(kb, wl)
    for r in reqs[:n_long]:
        r.max_new_tokens = long_new
    return reqs


def _run_preemption_engine(cfg, params, kb, n_req, pool_blocks,
                           preempt_iters):
    """One starved-workload run; returns (engine, stats, reqs,
    last-decode-logits-per-rid)."""
    eng = make_engine(
        cfg, params, None, strategy="all", use_focus=False,
        sched=SchedulerConfig(max_batch_tokens=100_000,
                              max_decode_batch=4,
                              max_prefill_batch=2,
                              preempt_after_iters=preempt_iters),
        pool_blocks=pool_blocks, decode_bucket_b=4, seq_bucket=512,
        trace_decode=True)
    reqs = _starved_workload(kb, n_req)
    stats = eng.run(reqs)
    last = {}
    for step_logits in eng.decode_trace:
        last.update(step_logits)
    return eng, stats, reqs, last


def _preemption_compare(cfg, params, kb, n_req, starved_blocks=20):
    """Preemption off vs on on a pool-starved workload, both compared
    against an unpressured (large-pool) reference run for output and
    final-logits bit-equality. Returns the per-mode gate numbers."""
    # reference: same workload, pool large enough that nothing stalls
    # (also warms every jit shape the starved runs will hit)
    _eng, ref_stats, ref_reqs, ref_last = _run_preemption_engine(
        cfg, params, kb, n_req, pool_blocks=4096, preempt_iters=0)
    assert ref_stats.failed == 0, "reference run must be unpressured"
    ref_out = {r.rid: list(r.output_tokens) for r in ref_reqs}

    out = {}
    for label, preempt_iters in (("off", 0), ("on", 4)):
        eng, stats, reqs, last = _run_preemption_engine(
            cfg, params, kb, n_req, pool_blocks=starved_blocks,
            preempt_iters=preempt_iters)
        c = eng.counters
        done = all(r.state == State.DONE for r in reqs)
        logits_ok = done and set(last) == set(ref_last) and all(
            np.array_equal(last[rid], ref_last[rid]) for rid in last)
        outputs_ok = done and all(
            list(r.output_tokens) == ref_out[r.rid] for r in reqs)
        p99_wait = queue_wait_p99(reqs)
        emit(f"fig22_preemption_{label}", p99_wait * 1e6,
             f"preemptions={c.preemptions};"
             f"head_stall_iters_max={c.head_stall_iters_max};"
             f"preempt_block_recovered={c.preempt_block_recovered};"
             f"p99_queue_wait_s={p99_wait:.3f};"
             f"ttft_p99_s={ttft_p99(reqs):.3f};"
             f"completed={stats.completed};failed={stats.failed};"
             f"logits_match_unpressured={logits_ok}")
        out[label] = dict(
            preemptions=c.preemptions,
            head_stall_iters_max=c.head_stall_iters_max,
            preempt_block_recovered=c.preempt_block_recovered,
            p99_queue_wait_s=p99_wait,
            ttft_p99_s=ttft_p99(reqs),
            completed=stats.completed, failed=stats.failed,
            logits_match_unpressured=bool(logits_ok),
            outputs_match_unpressured=bool(outputs_ok))
    _record_trajectory(
        "BENCH_preemption.json",
        dict(n_req=n_req, pool_blocks=starved_blocks, **{
            f"{k}_{label}": v for label, d in out.items()
            for k, v in d.items()}))
    return out


# ---- tensor-parallel sharded serving (PR 6 tentpole) ------------------------
# The parent process has already initialized jax on one device, so the
# 4-device comparison runs in a child with XLA_FLAGS set before the
# first jax import. The child runs the SAME workload unsharded and
# head-sharded and reports the gate numbers as one JSON line.
_SHARDED_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "src")
import json
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
    return eng, reqs, stats

e1, r1, s1 = run(None)
e2, r2, s2 = run(make_serving_mesh(4))
tokens_equal = all(a.output_tokens == b.output_tokens
                   for a, b in zip(r1, r2))
logits_equal = len(e1.decode_trace) == len(e2.decode_trace) > 0 and all(
    set(da) == set(db) and all(np.array_equal(da[k], db[k]) for k in da)
    for da, db in zip(e1.decode_trace, e2.decode_trace))

def side(eng, stats):
    return dict(kv_shards=eng.kv_shards,
                completed=stats.completed, failed=stats.failed,
                kv_bytes_device=int(eng.pool.peak_kv_bytes_per_device()),
                attn_flops_device=int(eng.counters.attn_flops_device),
                attn_flops_total=int(eng.counters.attn_flops_total))

print(json.dumps(dict(tokens_equal=bool(tokens_equal),
                      logits_equal=bool(logits_equal),
                      onedev=side(e1, s1), fourdev=side(e2, s2))))
"""


def _sharded_compare():
    """Unsharded vs head-sharded serving on a forced 4-device host mesh
    (subprocess, see ``_SHARDED_CHILD``). Emits
    ``fig22_sharded_{1dev,4dev}`` (per-device KV bytes + attention
    FLOPs), appends the trajectory to ``results/BENCH_sharded.json``,
    and returns the child's gate numbers."""
    import subprocess
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_CHILD],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        print(r.stderr[-3000:], file=sys.stderr)
        raise RuntimeError("sharded bench subprocess failed")
    res = json.loads(r.stdout.strip().splitlines()[-1])
    for label, s in (("1dev", res["onedev"]), ("4dev", res["fourdev"])):
        emit(f"fig22_sharded_{label}", float(s["attn_flops_device"]),
             f"kv_shards={s['kv_shards']};"
             f"kv_bytes_device={s['kv_bytes_device']};"
             f"attn_flops_device={s['attn_flops_device']};"
             f"attn_flops_total={s['attn_flops_total']};"
             f"completed={s['completed']};failed={s['failed']};"
             f"logits_equal={res['logits_equal']}")
    _record_trajectory(
        "BENCH_sharded.json",
        dict(tokens_equal=res["tokens_equal"],
             logits_equal=res["logits_equal"],
             **{f"{k}_1dev": v for k, v in res["onedev"].items()},
             **{f"{k}_4dev": v for k, v in res["fourdev"].items()}))
    return res


def run(quick: bool = False):
    cfg, params = get_trained_model()
    kb, retr, sys_t, rng = make_world(cfg)
    n_req = 10 if quick else 24
    loads = (240,) if quick else (60, 240, 960)
    for qpm in loads:
        for name, exkw in METHODS.items():
            store = None if name == "full" else fresh_store(f"tl-{name}")
            sched = SchedulerConfig(max_batch_tokens=4096,
                                    max_decode_batch=4)
            _eng, stats, thr, lat, ttft = _measure(cfg, params, store,
                                                   sched, exkw, kb,
                                                   n_req, qpm)
            saved = 1 - stats.prefill_tokens_computed / \
                max(1, stats.prefill_tokens_total)
            emit(f"fig22_qpm{qpm}_{name}", lat * 1e6,
                 f"throughput_rps={thr:.3f};mean_e2e_s={lat:.3f};"
                 f"mean_ttft_s={ttft:.3f};tokens_saved={saved:.2f}")

    _admission_compare(cfg, params, kb, n_req)
    _shared_blocks_compare(cfg, params, kb, n_req)
    _preemption_compare(cfg, params, kb, n_req=6 if quick else 10)
    _sharded_compare()


def ci_smoke() -> int:
    """CI perf gate matrix (ROADMAP). Returns a process exit code.

    The gates:

    * admission — packed admission throughput must not fall below
      ``CI_SMOKE_TOLERANCE * serial`` (wall-clock-derived, so shared CI
      runners add noise on top of the real ~1.5x effect; default tol
      1.0 is the strict local threshold).
    * shared blocks — zero-copy sharing must reserve strictly fewer
      blocks at admission than the copy path on an overlapping-chunk
      workload, with shared (refcount > 1) blocks actually observed
      (count-based as well).
    * preemption — on a pool-starved workload, preemption-on must
      actually preempt (preemptions > 0), complete every request with
      zero FAILED states, produce final decode logits bit-identical to
      an unpressured run, and bound the head-of-line stall (strictly
      lower max consecutive head-stall iteration count than
      preemption-off — the count-based stand-in for the p99 wait,
      which is emitted but not gated because it is wall-clock-derived).
    * sharded — the head-sharded engine on a forced 4-device host mesh
      must produce identical output tokens and bit-identical traced
      decode logits vs the single-device run, with strictly fewer
      per-device KV bytes and attention FLOPs and an unchanged total
      FLOP count (pure repartitioning; all count-based).
    * quant — quantized cpu/ssd tiers vs fp32 at an equal byte budget:
      strictly fewer DEEP (SSD) tier misses on the identical seeded
      workload (count-based capacity gate), plus the quality gate —
      ROUGE-L delta vs the fp32 lane <= eps at an exactly matched
      recompute ratio, with dequantized reads actually exercised
      (``dequant_loads > 0``). Trajectory in
      ``results/BENCH_quant.json``.
    * serve — the online serving front end (``benchmarks.serve_bench``):
      >= 24 multi-turn mixed-tenant requests over real HTTP with
      streamed tokens bit-identical to the offline ``Engine.run``
      replay, one mid-decode cancel delivering a strict prefix with
      zero reserved blocks afterwards, zero FAILED, per-tenant TTFT /
      queue-wait p99 rollups present. Trajectory in
      ``results/BENCH_serve.json``.
    * frontier — the quality-vs-recompute frontier on the
      reordered-context workload
      (``quality_vs_recompute.frontier_compare``): some blend
      (CacheBlend fusion) point must reach ROUGE-L within eps of the
      cachecraft anchor point at a STRICTLY lower recompute-token
      count (count-based). Trajectory in
      ``results/BENCH_frontier.json``.

    Gate numbers land in ``results/fig22_ci_smoke.json`` so CI can
    upload them as a workflow artifact."""
    tol = float(os.environ.get("CI_SMOKE_TOLERANCE", "1.0"))
    cfg, params = get_trained_model()
    kb, _retr, _sys_t, _rng = make_world(cfg)

    thr = _admission_compare(cfg, params, kb, n_req=8)
    ok_adm = thr["packed"] >= tol * thr["serial"]

    shb = _shared_blocks_compare(cfg, params, kb, n_req=8)
    ok_shared = (
        shb["zerocopy"]["blocks_reserved_total"]
        < shb["copy"]["blocks_reserved_total"]
        and shb["zerocopy"]["shared_blocks_peak"] > 0)

    pre = _preemption_compare(cfg, params, kb, n_req=5)
    # reported, not gated: wall-clock-derived, so noisy on shared
    # runners (the head-stall count below is the robust stand-in)
    pre["p99_wait_lower"] = (
        pre["on"]["p99_queue_wait_s"] < pre["off"]["p99_queue_wait_s"])
    ok_pre = (
        pre["on"]["preemptions"] > 0
        and pre["on"]["failed"] == 0 and pre["on"]["completed"] == 5
        and pre["off"]["failed"] == 0      # the comparison is moot if
        and pre["off"]["completed"] == 5   # deferral lost requests
        and pre["on"]["logits_match_unpressured"]
        and pre["on"]["outputs_match_unpressured"]
        and pre["on"]["head_stall_iters_max"]
        < pre["off"]["head_stall_iters_max"])

    from benchmarks.preloading import eviction_compare, preload_compare
    ev = eviction_compare(quick=True)
    # fully deterministic (seeded access sequence, count-based): the
    # reuse-aware policy must take strictly fewer tier misses than LRU
    # on the skewed chunk workload
    ok_evict = ev["reuse"]["tier_misses"] < ev["lru"]["tier_misses"]

    pl = preload_compare(quick=True)
    # count-based primary gate (hidden layers exist + strictly fewer
    # blocking awaits); the measured exposed-time comparison rides
    # along — the fixed per-load latency keeps its margin wide
    ok_preload = (
        pl["layerwise"]["hidden_layers"] > 0
        and pl["layerwise"]["blocked_layers"]
        < pl["eager"]["blocked_layers"]
        and pl["layerwise"]["load_exposed_s"]
        < pl["eager"]["load_exposed_s"])

    from benchmarks.preloading import eviction_quant_compare
    from benchmarks.quality_vs_recompute import quant_quality_compare
    evq = eviction_quant_compare(quick=True)
    qq = quant_quality_compare(quick=True)
    # capacity: strictly fewer deep misses at the same byte budget;
    # quality: score delta vs fp32 within eps at matched recompute,
    # with the dequant read path actually exercised
    ok_quant = (
        evq["int8"]["deep_misses"] < evq["fp32"]["deep_misses"]
        and qq["matched_recompute"]
        and abs(qq["delta"]) <= qq["eps"]
        and qq["int8"]["dequant_loads"] > 0)
    _record_trajectory(
        "BENCH_quant.json",
        dict(deep_misses_fp32=evq["fp32"]["deep_misses"],
             deep_misses_int8=evq["int8"]["deep_misses"],
             byte_budget=evq["int8"]["byte_budget"],
             quant_bytes_saved=evq["int8"]["quant_bytes_saved"],
             rouge_fp32=qq["fp32"]["rouge"],
             rouge_int8=qq["int8"]["rouge"],
             rouge_delta=qq["delta"], eps=qq["eps"],
             recompute_ratio=qq["int8"]["recompute"],
             dequant_loads=qq["int8"]["dequant_loads"]))

    from benchmarks.quality_vs_recompute import frontier_compare
    # blend must beat the cachecraft anchor on token count at matched
    # quality on the rotated workload (fr["ok"]; trajectory appended
    # inside frontier_compare to results/BENCH_frontier.json)
    fr = frontier_compare(quick=True)

    from benchmarks.serve_bench import serve_gate
    # the online front end must be a faithful serving of Engine.run:
    # every HTTP-streamed token sequence bit-identical to the offline
    # replay, a mid-decode HTTP cancel delivering a strict prefix with
    # the pool settled (zero reserved), per-tenant p99 rollups present
    # (sv["ok"]; trajectory in results/BENCH_serve.json)
    sv = serve_gate()

    sh = _sharded_compare()
    # bit-equality + strictly-fewer-per-device-work, all count-based:
    # the sharded engine must be a pure repartitioning of the same math
    ok_sharded = (
        sh["tokens_equal"] and sh["logits_equal"]
        and sh["onedev"]["failed"] == 0 and sh["fourdev"]["failed"] == 0
        and sh["fourdev"]["kv_bytes_device"]
        < sh["onedev"]["kv_bytes_device"]
        and sh["fourdev"]["attn_flops_device"]
        < sh["onedev"]["attn_flops_device"]
        and sh["fourdev"]["attn_flops_total"]
        == sh["onedev"]["attn_flops_total"])

    gates = {
        "admission": dict(ok=ok_adm, tolerance=tol, **{
            f"throughput_rps_{k}": v for k, v in thr.items()}),
        "shared_blocks": dict(ok=ok_shared, copy=shb["copy"],
                              zerocopy=shb["zerocopy"]),
        "preemption": dict(ok=ok_pre, off=pre["off"], on=pre["on"],
                           p99_wait_lower=pre["p99_wait_lower"]),
        "eviction": dict(ok=ok_evict, lru=ev["lru"], reuse=ev["reuse"]),
        "preload": dict(ok=ok_preload, eager=pl["eager"],
                        layerwise=pl["layerwise"]),
        "sharded": dict(ok=ok_sharded, tokens_equal=sh["tokens_equal"],
                        logits_equal=sh["logits_equal"],
                        onedev=sh["onedev"], fourdev=sh["fourdev"]),
        "serve": sv,
        "frontier": dict(ok=fr["ok"], eps=fr["eps"],
                         anchor=fr["anchor"], blend_win=fr["blend_win"]),
        "quant": dict(ok=ok_quant, capacity_fp32=evq["fp32"],
                      capacity_int8=evq["int8"],
                      rouge_fp32=qq["fp32"]["rouge"],
                      rouge_int8=qq["int8"]["rouge"],
                      rouge_delta=qq["delta"], eps=qq["eps"],
                      matched_recompute=qq["matched_recompute"],
                      dequant_loads=qq["int8"]["dequant_loads"]),
    }
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "fig22_ci_smoke.json"), "w") as f:
        json.dump(gates, f, indent=2)

    for name, g in gates.items():
        print(f"# ci-smoke[{name}]: "
              f"{'OK' if g['ok'] else 'FAIL'} "
              f"{ {k: v for k, v in g.items() if k != 'ok'} }",
              file=sys.stderr)
    return 0 if all(g["ok"] for g in gates.values()) else 1


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--ci-smoke", action="store_true",
                    help="run the CI perf gates (admission throughput, "
                         "copy-vs-zerocopy reserved blocks, preemption counts + logits "
                         "bit-equality, eviction tier misses, preload "
                         "overlap, sharded bit-equality + per-device "
                         "FLOPs/bytes, quantized-tier capacity + "
                         "quality delta, online-serve HTTP streaming "
                         "bit-equality + mid-decode cancel, blend-vs-"
                         "cachecraft recompute frontier); writes "
                         "results/fig22_ci_smoke.json; exit 1 on any "
                         "gate failure")
    args = ap.parse_args()
    if args.ci_smoke:
        raise SystemExit(ci_smoke())
    run(quick=args.quick)
