"""Shared benchmark infrastructure.

Quality benches run on a tiny llama-family model *trained to
convergence* on the synthetic Markov corpus (so attention develops the
intra>inter locality real LMs show — random-init models are adversarial
for cache reuse and would understate every method). The trained
checkpoint is cached under results/bench_model/.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_tiny                                 # noqa
from repro.core.chunkstore import ChunkStore                       # noqa
from repro.core.prefill import CacheCraftExecutor, pack_cache      # noqa
from repro.core.tiers import TieredStore                           # noqa
from repro.models import model as M                                # noqa
from repro.serving.api import EngineSpec, build_engine             # noqa
from repro.serving.metrics import rouge_l_f1, relative_deviation   # noqa
from repro.serving.rag import KnowledgeBase, Retriever, make_question  # noqa
from repro.training import checkpoint as ckpt                      # noqa
from repro.training.data import DataConfig, SyntheticLM            # noqa
from repro.training.optimizer import AdamWConfig                   # noqa
from repro.training.steps import (init_train_state, make_train_step,  # noqa
                                  state_to_tree, tree_to_state)

BENCH_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                         "bench_model")


def bench_config():
    return get_tiny("llama3-8b")


def get_trained_model(steps: int = 300, seed: int = 0):
    """Train (or load) the tiny quality-bench model."""
    cfg = bench_config()
    if ckpt.latest_step(BENCH_DIR) is not None:
        tree = ckpt.restore(BENCH_DIR)
        return cfg, tree["params"]
    data = SyntheticLM(DataConfig(seq_len=128, global_batch=8,
                                  vocab_size=cfg.vocab_size, seed=seed))
    state = init_train_state(cfg, jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(
        cfg, AdamWConfig(peak_lr=1e-3, warmup_steps=20, total_steps=steps)))
    t0 = time.time()
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        state, m = step(state, b)
    print(f"# trained bench model: {steps} steps, "
          f"final loss {float(m['loss']):.3f}, {time.time()-t0:.0f}s",
          file=sys.stderr)
    ckpt.save({"params": state.params}, BENCH_DIR, steps)
    return cfg, state.params


def make_world(cfg, n_chunks: int = 24, seed: int = 0):
    kb = KnowledgeBase(num_chunks=n_chunks, vocab_size=cfg.vocab_size,
                       chunk_len_min=24, chunk_len_max=40, seed=seed)
    retr = Retriever(kb, k=4, zipf_a=1.1, seed=seed)
    rng = np.random.default_rng(seed)
    sys_tokens = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    return kb, retr, sys_tokens, rng


def fresh_store(tmp_suffix: str, n=100, m=5, alpha=1.0,
                hbm=1 << 30, cpu=1 << 30,
                tier_dtypes: Optional[Dict[str, str]] = None) -> ChunkStore:
    """``tier_dtypes`` passes through to ``TieredStore`` (quantized
    cpu/ssd tiers; ``None`` keeps the legacy fp32 pass-through)."""
    import tempfile
    d = tempfile.mkdtemp(prefix=f"cc-{tmp_suffix}-")
    return ChunkStore(TieredStore(hbm, cpu, d, start_worker=False,
                                  tier_dtypes=tier_dtypes),
                      n_chunks=n, m_variants=m, alpha=alpha)


def record_trajectory(fname, entry):
    """Append one run's numbers to ``results/<fname>`` (a bench
    trajectory: one JSON list entry per invocation, so regressions show
    as a trend, not just a point)."""
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        fname)
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
        except (ValueError, OSError):
            history = []
    entry = dict(entry, run_index=len(history))
    history.append(entry)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(history, f, indent=2)


def make_engine(cfg, params, store, **spec_kw):
    """Construct an ``Engine`` through the typed serving API
    (``EngineSpec``/``build_engine``) with the bench cfg/params/store
    injected — benchmarks hand in the trained model and their own
    per-bench stores rather than letting the spec rebuild them.
    ``spec_kw`` are ``EngineSpec`` fields (strategy, sched,
    pool_blocks, ...)."""
    return build_engine(EngineSpec(**spec_kw), cfg=cfg, params=params,
                        store=store)


@functools.lru_cache(maxsize=None)
def _decode_step(cfg):
    """Jitted one-token decode on a contiguous cache."""
    @jax.jit
    def decode_step(params, tokens, positions, cache):
        out = M.decode_step(cfg, params, tokens, positions, cache)
        return out.logits, out.cache
    return decode_step


def greedy_continue(cfg, params, res, n_tokens: int) -> List[int]:
    """Greedy decode continuing from an executor PrefillResult."""
    step = _decode_step(cfg)
    S = res.k_layers.shape[1]
    # k_layers is exact-length (total_len); leave room for every decode
    # write plus slack so no token scatter lands out of bounds
    pad = max(8, res.total_len - S + n_tokens + 8)
    k = np.pad(res.k_layers, ((0, 0), (0, pad), (0, 0), (0, 0)))
    v = np.pad(res.v_layers, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pos = np.pad(res.pos_layout, (0, pad), constant_values=-1)
    cache = pack_cache(cfg, k, v, pos)
    toks = [int(np.argmax(res.logits_last[:cfg.vocab_size]))]
    p = res.total_len
    for i in range(n_tokens - 1):
        logits, cache = step(params, jnp.asarray([toks[-1]]),
                             jnp.asarray([p], jnp.int32), cache)
        toks.append(int(np.argmax(
            np.asarray(logits[0, 0, :cfg.vocab_size]))))
        p += 1
    return toks


@dataclass
class EvalCase:
    chunks: List[np.ndarray]
    question: np.ndarray


def build_cases(kb, retr, rng, n_cases: int, qlen: int = 12,
                seed_base: int = 0) -> List[EvalCase]:
    cases = []
    for i in range(n_cases):
        ids = retr.retrieve(seed_base + i)
        q = make_question(rng, kb, ids, qlen)
        cases.append(EvalCase(chunks=retr.chunks_for(ids), question=q))
    return cases


def timed(fn, *args, reps: int = 1, **kw):
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) / reps


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
