"""Serving launcher: batch replay or a live HTTP server, both built
through the one typed front door (``serving.api.EngineSpec``).

Batch replay (default): generate a synthetic RAG workload, run it
through the engine with continuous batching, print per-request and
aggregate stats::

    python -m repro.launch.serve --requests 24 --qpm 240

Online serving (``--serve``): boot the engine on a background stepping
thread behind the stdlib HTTP API (see ``serving/server.py`` for the
threading/ownership contract), then drive it from anywhere::

    # terminal 1 — tiny config, random-init params, port 8763
    python -m repro.launch.serve --serve --port 8763

    # terminal 2 — submit, stream tokens as NDJSON, inspect stats
    curl -s localhost:8763/v1/submit -d '{
        "system_tokens": [1,2,3], "chunk_tokens": [[4,5,6],[7,8]],
        "question_tokens": [9,10], "max_new_tokens": 8,
        "tenant": "gold", "deadline_s": 2.0}'
    # -> {"rid": 0}
    curl -sN localhost:8763/v1/stream/0      # {"token": ...} per line,
                                             # then {"done": true, ...}
    curl -s -X POST localhost:8763/v1/cancel/0
    curl -s localhost:8763/stats | python -m json.tool

Full-size configs: ``--full`` (the old ``--tiny`` flag was
``store_true`` with ``default=True`` — permanently on, so full-size
was unreachable from the CLI).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.serving.api import EngineSpec, build_engine
from repro.serving.rag import KnowledgeBase
from repro.serving.workload import TenantSpec, WorkloadConfig, generate


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    # engine construction (consumed by EngineSpec.from_args)
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default is the tiny one)")
    from repro.core.strategies import STRATEGIES
    ap.add_argument("--strategy", default="cachecraft",
                    choices=tuple(STRATEGIES),
                    help="recompute strategy (core.strategies registry): "
                         + ", ".join(STRATEGIES))
    ap.add_argument("--recompute", type=float, default=None)
    ap.add_argument("--no-focus", action="store_true")
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--layerwise-load", action="store_true")
    ap.add_argument("--pool-blocks", type=int, default=8192)
    ap.add_argument("--max-batch-tokens", type=int, default=8192)
    ap.add_argument("--max-decode-batch", type=int, default=4)
    ap.add_argument("--tier-dtypes", default=None,
                    help='per-tier storage codecs, e.g. "cpu=int8,ssd=fp8"')
    ap.add_argument("--params", default=None,
                    help="checkpoint dir with trained params")
    ap.add_argument("--seed", type=int, default=0)
    # workload (batch replay)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--qpm", type=float, default=240)
    ap.add_argument("--kb-chunks", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--turns", type=int, default=1,
                    help=">1: multi-turn sessions with growing history")
    ap.add_argument("--tenants", default=None,
                    help='mixed-tenant trace, e.g. "gold:3:2.0,free:1:8.0" '
                         "(name:weight:deadline_s)")
    # online serving
    ap.add_argument("--serve", action="store_true",
                    help="run the HTTP server instead of batch replay")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8763)
    return ap


def parse_tenants(s):
    if not s:
        return None
    out = []
    for part in s.split(","):
        name, weight, deadline = (part.split(":") + ["1", "0"])[:3]
        out.append(TenantSpec(name, float(weight), float(deadline)))
    return out


def main():
    args = make_parser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    spec = EngineSpec.from_args(args)
    eng = build_engine(spec)
    kb = KnowledgeBase(num_chunks=args.kb_chunks,
                       vocab_size=eng.cfg.vocab_size, seed=args.seed)

    if args.serve:
        from repro.serving.server import CacheCraftServer
        srv = CacheCraftServer(eng, host=args.host, port=args.port).start()
        print(f"serving {args.arch}{'' if args.full else ' (tiny)'} "
              f"strategy={spec.strategy} on {srv.url}")
        print("routes: POST /v1/submit | GET /v1/stream/<rid> | "
              "POST /v1/cancel/<rid> | GET /health | GET /stats")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            print("\nshutting down...")
            srv.shutdown()
        return

    reqs = generate(kb, WorkloadConfig(
        num_requests=args.requests, qpm=args.qpm, seed=args.seed,
        max_new_tokens=args.max_new, k_chunks=5, turns=args.turns,
        tenants=parse_tenants(args.tenants)))
    t0 = time.time()
    stats = eng.run(reqs)
    wall = time.time() - t0
    done = [r for r in reqs if r.e2e_latency is not None]
    print(f"\n== {spec.strategy} | {args.requests} reqs @ {args.qpm} QPM ==")
    print(f"completed {stats.completed} failed {stats.failed} "
          f"wall {wall:.1f}s simclock {stats.clock:.2f}s")
    print(f"prefill tokens: total {stats.prefill_tokens_total} "
          f"computed {stats.prefill_tokens_computed} "
          f"(saved {1 - stats.prefill_tokens_computed / max(1, stats.prefill_tokens_total):.1%})")
    if done:
        print(f"TTFT mean {np.mean([r.ttft for r in done])*1e3:.1f}ms "
              f"p99 {np.percentile([r.ttft for r in done], 99)*1e3:.1f}ms")
        print(f"e2e mean {np.mean([r.e2e_latency for r in done]):.3f}s  "
              f"throughput {len(done)/max(stats.clock, 1e-9):.2f} req/s")
    if eng.store:
        store = eng.store
        print(f"store: {store.num_variants()} variants over "
              f"{len(store.table)} chunks, evictions {store.evictions}, "
              f"tier hits {store.tiers.stats['hits']}")


if __name__ == "__main__":
    main()
