#!/usr/bin/env python3
"""Smoke check of the serving path on a TPU at Llama-3-8B widths.

Run from the repository root::

    python chip_smoke.py            # one chip: every phase below
    python chip_smoke.py --chips 4  # four chips: the head-sharded phase only

It drives ``build_engine`` -> scheduler -> ``CacheCraftExecutor`` ->
KVPool / chunk store -> decode once, in one process, and fails (non-zero
exit, no result line) if any phase fails. There is no CPU fallback: on
any platform other than a TPU it exits non-zero and names the platform.

The model is ``llama3-8b`` at every published width (d_model 4096,
32/8 heads of 128, d_ff 14336, vocab 128256) with random weights from
``--seed``, cut in depth to fit one v5e's 16 GiB (see ``LAYERS``).

Phases (one chip):

* engine — Cache-Craft serving of 8 RAG requests (5 chunks of 256-512
  tokens each), run twice on one engine: all complete, chunk caches hit,
  fewer prefill tokens computed than served. Both wall times are
  printed as smoke timings, not benchmark results.
* reference — the full-recompute strategy's first-token logits against
  a plain cacheless forward at ``highest`` matmul precision.
* kernels — the same requests with the Pallas chunk-attention prefill
  and the Pallas paged decode kernel, against the dense run; the
  compiled engine steps must contain ``tpu_custom_call``.
* server — ``CacheCraftServer`` streams 3 requests over HTTP; the
  streams must equal an offline ``Engine.run`` of the same requests.

The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from dataclasses import dataclass

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Depth cut. Every width stays published. At 8 layers the fp32 weights
# are 10.4 GiB, and compiling the engine's paged decode step for a v5e
# fails: 16.26 GiB of the chip's 15.75 GiB (weights and the 1 GiB pool
# twin, plus 4.6 GiB of temporaries, mostly bf16 copies of the weights
# that default-precision matmuls convert to). At 6 layers (8.8 GiB of
# weights) every step compiles with room to spare.
LAYERS = 6

# Agreement bound for logits, as a relative L2 error ||a - b|| / ||b||.
# The engine's f32 matmuls run at the TPU's default precision (bf16
# passes, ~2^-9 relative rounding per operand); through 6 layers and a
# 128k-wide head that compounds to O(1e-2). A wrong mask, position or
# cached-KV row gives O(1). 5e-2 separates the two.
REL_L2_BOUND = 5e-2


@dataclass(frozen=True)
class Sizes:
    """Traffic and engine sizing. ``CHIP`` is the smoke's own."""
    kb_chunks: int = 16
    chunk_len: tuple = (256, 512)
    requests: int = 8
    k_chunks: int = 5
    max_new: int = 16
    pool_blocks: int = 1024
    block_size: int = 16
    # coarse decode buckets keep the number of compiled shapes small
    seq_bucket: int = 1024
    decode_bucket_b: int = 8
    ref_bucket: int = 256
    server_requests: int = 3


CHIP = Sizes()


def log(msg: str):
    print(msg, flush=True)


def check(cond, msg=""):
    """A smoke condition; fails the run (unlike ``assert``, also under
    ``python -O``)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def require_tpu(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind}); "
                         f"there is no CPU fallback")
    if len(devs) < n:
        raise SystemExit(f"chip_smoke: needs {n} TPU chips, found "
                         f"{len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------
def smoke_config():
    from repro.configs import get_config
    return get_config("llama3-8b").replace(num_layers=LAYERS)


def make_requests(cfg, sizes: Sizes, seed: int):
    """Fresh Request objects for the smoke's workload (same every call)."""
    from repro.serving.rag import KnowledgeBase
    from repro.serving.workload import WorkloadConfig, generate
    kb = KnowledgeBase(num_chunks=sizes.kb_chunks,
                       chunk_len_min=sizes.chunk_len[0],
                       chunk_len_max=sizes.chunk_len[1],
                       vocab_size=cfg.vocab_size, seed=seed)
    return generate(kb, WorkloadConfig(
        num_requests=sizes.requests, k_chunks=sizes.k_chunks,
        max_new_tokens=sizes.max_new, qpm=1e9, seed=seed))


def build(cfg, params, sizes: Sizes, *, strategy="cachecraft", **over):
    """An engine for the smoke: one prefill per iteration (the dense
    prefill window's score matrix for one 2.6k-token request already
    takes 3.5 GiB), focus tracking off (one layer window per pass), and
    decode logits traced for the comparisons."""
    from repro.serving.api import EngineSpec, StoreSpec, build_engine
    from repro.serving.scheduler import SchedulerConfig
    spec = EngineSpec(
        strategy=strategy, use_focus=False,
        pool_blocks=sizes.pool_blocks, block_size=sizes.block_size,
        seq_bucket=sizes.seq_bucket, decode_bucket_b=sizes.decode_bucket_b,
        sched=SchedulerConfig(max_prefill_batch=1,
                              max_decode_batch=sizes.requests),
        trace_decode=True,
        store=StoreSpec(hbm_bytes=4 << 30, cpu_bytes=8 << 30,
                        n_chunks=4 * sizes.kb_chunks), **over)
    return build_engine(spec, cfg=cfg, params=params)


def retire(eng):
    """Stop an engine's chunk-store workers and collect what the caller
    has dropped, so the next engine starts with the device memory free."""
    if eng.store is not None:
        eng.store.tiers.close()
    gc.collect()


def run_workload(eng, reqs):
    t0 = time.perf_counter()
    stats = eng.run(reqs)
    return stats, time.perf_counter() - t0


def assert_served(stats, reqs, n):
    from repro.serving.request import State
    check(stats.completed == n and stats.failed == 0,
          f"completed {stats.completed}/{n}, failed {stats.failed}")
    check(all(r.state == State.DONE and
              len(r.output_tokens) == r.max_new_tokens for r in reqs),
          "a request did not finish its output")


def token_logits(eng, reqs):
    """rid -> [max_new, V]: the logits behind each output token. Row 0
    is the first token's (the request's prefill rerun through the
    engine's executor at the same shapes; its argmax must be the token
    the engine emitted), rows 1.. the decode trace."""
    V = eng.cfg.vocab_size
    out = {}
    for r in reqs:
        res = eng.executor.process(r.system_tokens, r.chunk_tokens,
                                   r.question_tokens)
        first = np.asarray(res.logits_last[:V])
        check(int(np.argmax(first)) == r.output_tokens[0],
              f"rid {r.rid}: rerun prefill disagrees with the engine")
        out[r.rid] = [first]
    for step in eng.decode_trace:
        for rid, lg in step.items():
            out[rid].append(lg[:V])
    return {rid: np.stack(v) for rid, v in out.items()}


def rel_l2(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def compare_runs(name, run_a, run_b):
    """Run b against run a (each ``(requests, token_logits)``), request
    by request: output tokens equal, and the logits behind every token
    both runs produced from the same prefix within ``REL_L2_BOUND``. A
    token may differ only where those logits agree within the bound (a
    numerical near-tie: the argmax flips on an error the bound allows);
    the request's later tokens then follow different prefixes and are
    not compared. Returns the worst error."""
    (reqs_a, logits_a), (reqs_b, logits_b) = run_a, run_b
    worst, equal, compared, ties = 0.0, 0, 0, []
    for ra, rb in zip(reqs_a, reqs_b):
        ta, tb = ra.output_tokens, rb.output_tokens
        la, lb = logits_a[ra.rid], logits_b[rb.rid]
        for i in range(len(ta)):
            err = rel_l2(lb[i], la[i])
            worst = max(worst, err)
            compared += 1
            check(err < REL_L2_BOUND,
                  f"{name}: rid {ra.rid} token {i} logits rel L2 {err:.3e}")
            if ta[i] != tb[i]:
                ties.append((ra.rid, i))
                break
            equal += 1
    total = sum(len(r.output_tokens) for r in reqs_a)
    log(f"{name}: {equal}/{total} output tokens equal, logits of "
        f"{compared} tokens compared, worst rel L2 {worst:.3e} (bound "
        f"{REL_L2_BOUND:g}); near-tie divergences at (rid, token) "
        f"{ties}")
    check(equal > 0, f"{name}: no output token equal")
    return worst


class StepRecorder:
    """Wraps an engine's jitted step and keeps the abstract arguments of
    its last call, so the compiled program at the shapes the engine
    actually ran can be inspected afterwards."""

    def __init__(self, fn):
        self.fn = fn
        self.last = None

    def __call__(self, *args, **kw):
        import jax

        def spec(x):
            if isinstance(x, jax.Array):
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding)
            if isinstance(x, np.ndarray):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x
        self.last = (jax.tree.map(spec, args), kw)
        return self.fn(*args, **kw)

    def compiled_text(self) -> str:
        args, kw = self.last
        return self.fn.lower(*args, **kw).compile().as_text()


def record_steps(eng):
    """Install recorders on the engine's prefill window and decode step."""
    rec = {"prefill window": StepRecorder(eng.executor._window),
           "paged decode step": StepRecorder(eng._paged_fn)}
    eng.executor._window = rec["prefill window"]
    eng._paged_fn = rec["paged decode step"]
    return rec


def assert_kernels_compiled(name, recorders):
    for step, rec in recorders.items():
        check(rec.last is not None, f"{name}: {step} never ran")
        n = rec.compiled_text().count("tpu_custom_call")
        check(n > 0, f"{name}: no tpu_custom_call in the compiled {step}")
        log(f"{name}: compiled {step} holds {n} tpu_custom_call sites")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def phase_engine(cfg, params, sizes: Sizes, seed: int):
    """Cache-Craft serving, run twice on one engine."""
    eng = build(cfg, params, sizes)
    times = []
    for attempt in (1, 2):
        reqs = make_requests(cfg, sizes, seed)
        stats, secs = run_workload(eng, reqs)
        assert_served(stats, reqs, sizes.requests)
        times.append(secs)
        hits = sum(r.cache_hits for r in reqs)
        log(f"engine run {attempt}: {stats.completed}/{sizes.requests} "
            f"completed, {stats.failed} failed, {hits} chunk-cache hits, "
            f"prefill tokens computed {stats.prefill_tokens_computed} of "
            f"{stats.prefill_tokens_total}")
        eng.stats = type(stats)()
    check(hits > 0, "no chunk-cache hit")
    check(stats.prefill_tokens_computed < stats.prefill_tokens_total,
          "every prompt token was recomputed")
    log(f"smoke timing (not a benchmark): engine run 1 took {times[0]:.2f} "
        f"s wall, compilation included; run 2 took {times[1]:.2f} s")
    retire(eng)
    return times


def reference_logits(cfg, params, reqs, bucket: int):
    """Plain cacheless forward of each prompt (system + chunks +
    question) at ``highest`` matmul precision; last-token logits.
    Prompts are left-padded to one length (padding at position -1 is
    masked), so one program serves every request."""
    import jax
    from repro.models import model as M
    prompts = [np.concatenate([r.system_tokens, *r.chunk_tokens,
                               r.question_tokens]).astype(np.int32)
               for r in reqs]
    T = -(-max(len(p) for p in prompts) // bucket) * bucket

    @jax.jit
    def fwd(params, tokens, positions):
        return M.forward(cfg, params, tokens=tokens, positions=positions,
                         attn_impl="dense", logits_slice="last").logits

    out = []
    with jax.default_matmul_precision("highest"):
        for p in prompts:
            toks = np.zeros((1, T), np.int32)
            pos = np.full((1, T), -1, np.int32)
            toks[0, T - len(p):] = p
            pos[0, T - len(p):] = np.arange(len(p))
            out.append(np.asarray(fwd(params, toks, pos))[0, -1,
                                                          :cfg.vocab_size])
    return out


def phase_reference(cfg, params, sizes: Sizes, seed: int):
    """Full recompute (``all``) first-token logits vs the reference.
    Returns the dense ``all`` run (requests + token logits), which the
    kernel phase compares against."""
    eng = build(cfg, params, sizes, strategy="all")
    reqs = make_requests(cfg, sizes, seed)
    stats, _ = run_workload(eng, reqs)
    assert_served(stats, reqs, sizes.requests)
    dense = (reqs, token_logits(eng, reqs))
    retire(eng)
    del eng
    gc.collect()
    ref = reference_logits(cfg, params, reqs, sizes.ref_bucket)
    worst, exact = 0.0, 0
    for r, rf in zip(reqs, ref):
        lg = dense[1][r.rid][0]
        err = rel_l2(lg, rf)
        worst = max(worst, err)
        top, rtop = int(np.argmax(lg)), int(np.argmax(rf))
        exact += top == rtop
        # greedy top-1: the engine's token must be the reference's, or
        # tie with it to within the logits' own measured error
        tol = 2 * float(np.max(np.abs(lg - rf)))
        check(top == rtop or rf[top] >= rf[rtop] - tol,
              f"rid {r.rid}: top-1 {top} vs reference {rtop}")
        check(err < REL_L2_BOUND, f"rid {r.rid}: rel L2 {err:.3e}")
    log(f"reference: 'all' first-token logits vs highest-precision "
        f"forward, worst rel L2 {worst:.3e} (bound {REL_L2_BOUND:g}), "
        f"top-1 equal for {exact}/{len(reqs)} requests (the rest within "
        f"their measured error of a tie)")
    return dense


def phase_kernels(cfg, params, sizes: Sizes, seed: int, dense):
    """The Pallas route (chunk-attention prefill, paged decode kernel)
    on the same requests, against the dense run. Full recompute, so its
    plans match the dense run row for row (under cachecraft the kernel
    path scores chunks without key-side mass, and would legitimately
    pick other recompute rows)."""
    name = "paged_kernel"
    eng = build(cfg, params, sizes, strategy="all", attn_impl=name)
    rec = record_steps(eng)
    reqs = make_requests(cfg, sizes, seed)
    stats, _ = run_workload(eng, reqs)
    assert_served(stats, reqs, sizes.requests)
    compare_runs(f"{name} vs dense", dense, (reqs, token_logits(eng, reqs)))
    assert_kernels_compiled(name, rec)
    retire(eng)
    del eng, rec
    gc.collect()
    check_rope_kernel(cfg)


def check_rope_kernel(cfg):
    """The RoPE kernel (apply, then remove) against the model's own
    rotation at the config's head width."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.rope.ops import rope
    from repro.models.layers import apply_rope
    T = 512
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (T, cfg.num_kv_heads, cfg.head_dim_))
    pos = jnp.arange(T, dtype=jnp.int32) * 7
    got = rope(x, pos, theta=cfg.rope_theta)
    want = apply_rope(x, pos, cfg.rope_theta)
    back = rope(got, pos, theta=cfg.rope_theta, inverse=True)
    err, inv = rel_l2(got, want), rel_l2(back, x)
    check(err < 1e-3 and inv < 1e-5,
          f"rope kernel rel L2 {err:.3e}, apply+remove {inv:.3e}")
    log(f"rope kernel: rel L2 {err:.3e} vs model RoPE, {inv:.3e} after "
        f"apply+remove")


def phase_server(cfg, params, sizes: Sizes, seed: int):
    """HTTP streams vs an offline replay of the same requests. Requests
    run one at a time on both sides (the next is submitted when the
    previous stream ends; offline arrivals are spread far apart), so
    both engines see the same store state and the same step shapes."""
    from repro.serving.server import CacheCraftServer, ServeClient
    n = sizes.server_requests
    eng = build(cfg, params, sizes)
    srv = CacheCraftServer(eng).start()
    try:
        client = ServeClient(srv.host, srv.port, timeout=900.0)
        check(client.health()["ok"], "server unhealthy")
        streamed = []
        for req in make_requests(cfg, sizes, seed)[:n]:
            toks, state = client.stream(client.submit(req))
            check(state == "done", f"stream ended {state}")
            streamed.append(toks)
    finally:
        srv.shutdown()
        retire(eng)
    del srv, eng
    gc.collect()
    off = build(cfg, params, sizes)
    reqs = make_requests(cfg, sizes, seed)[:n]
    for i, r in enumerate(reqs):
        r.arrival_time = 1e6 * i
    stats, _ = run_workload(off, reqs)
    assert_served(stats, reqs, n)
    offline = [r.output_tokens for r in reqs]
    retire(off)
    del off
    gc.collect()
    check(streamed == offline, f"streams {streamed} vs offline {offline}")
    log(f"server: {n} HTTP streams equal the offline Engine.run "
        f"({sum(map(len, streamed))} tokens)")


def phase_sharded(cfg, params, sizes: Sizes, seed: int, n_dev: int):
    """Head-sharded serving on an ``n_dev``-chip mesh against the same
    requests on one device: ``sharded`` prefill with the paged gather
    decode under the mesh, then the Pallas route, whose paged decode
    kernel runs per shard."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import make_serving_mesh
    from repro.models import backend as AB
    eng = build(cfg, params, sizes, strategy="all")
    reqs_1 = make_requests(cfg, sizes, seed)
    stats, _ = run_workload(eng, reqs_1)
    assert_served(stats, reqs_1, sizes.requests)
    one = (reqs_1, token_logits(eng, reqs_1))
    retire(eng)
    del eng
    gc.collect()
    mesh = make_serving_mesh(n_dev)
    # replicate leaf by leaf, donating the device-0 original, so device
    # 0 never holds two full copies of the weights
    rep = NamedSharding(mesh, PartitionSpec())
    leaves, tree = jax.tree.flatten(params)
    params = None
    for i, x in enumerate(leaves):
        leaves[i] = jax.device_put(x, rep, donate=True)
    params = jax.tree.unflatten(tree, leaves)
    del leaves
    for name, over in (("sharded", {}),
                       ("sharded paged_kernel",
                        dict(attn_impl="paged_kernel"))):
        eng = build(cfg, params, sizes, strategy="all", mesh=mesh, **over)
        check(eng.kv_shards == n_dev, f"kv_shards {eng.kv_shards}")
        reqs = make_requests(cfg, sizes, seed)
        stats, _ = run_workload(eng, reqs)
        assert_served(stats, reqs, sizes.requests)
        compare_runs(f"{name} ({n_dev} chips) vs one device", one,
                     (reqs, token_logits(eng, reqs)))
        retire(eng)
        del eng
        gc.collect()
    AB.set_serving_mesh(None)
    for d in jax.devices()[:n_dev]:
        mem = d.memory_stats() or {}
        log(f"device {d.id} ({d.device_kind}): bytes_in_use "
            f"{mem.get('bytes_in_use')}, peak_bytes_in_use "
            f"{mem.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the head-sharded four-chip phase")
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    from repro.models import model as M
    cache_dir = enable_compile_cache()
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache_dir}")
    cfg = smoke_config()
    log(f"model: {cfg.name} at published widths, {cfg.num_layers} of 32 "
        f"layers (at 8 the paged decode step needs 16.26 GiB of the "
        f"chip's 15.75 GiB), fp32 random weights from seed {args.seed}")
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"params: {n} initialised on device in "
        f"{time.perf_counter() - t0:.1f} s")
    if args.chips == 4:
        phase_sharded(cfg, params, CHIP, args.seed, 4)
    else:
        phase_engine(cfg, params, CHIP, args.seed)
        dense = phase_reference(cfg, params, CHIP, args.seed)
        phase_kernels(cfg, params, CHIP, args.seed, dense)
        phase_server(cfg, params, CHIP, args.seed)
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
