"""Run one cell of the benchmark once and print its result.

    python3 -m chipbench.run --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a model configuration
and a traffic mix; both are found by name, as are the cell's output
limits (``chipbench/limits/<workload>.json``) and each per-layer
metric's reader (``chipbench/metrics/<metric>.py``).

One run, in one process:

1. Set-up (``setup_s``): weights from the seed on the device, sweeps
   that compile every prefill, decode and pool-upload shape the cell's
   traffic can reach, the engine (``serving.api.build_engine``) behind
   ``CacheCraftServer``, warm-up traffic from a separate seed stream
   that fills the chunk store, and a lead-in of the cell's own traffic
   over HTTP.
2. The window: ``--seconds`` of the cell's traffic, sent by client
   threads over HTTP and timed on the client's monotonic clock. With
   ``--trace 1`` the profiler records the first ``TRACE_SECONDS`` of it.
3. After the window: requests still in flight are awaited, the device's
   peak memory is read, the engine is freed, and the served tokens of a
   sample of finished requests are compared with the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``check``, every number compared with its
limit, which the last lines of standard error repeat. On any platform
other than a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse                                        # noqa: E402
import gc                                              # noqa: E402
import importlib.util                                  # noqa: E402
import json                                            # noqa: E402
import os                                              # noqa: E402
import shutil                                          # noqa: E402
import sys                                             # noqa: E402
import tempfile                                        # noqa: E402
from dataclasses import dataclass, field               # noqa: E402
from pathlib import Path                               # noqa: E402
from typing import Dict, List, Optional                # noqa: E402

import numpy as np                                     # noqa: E402

from chipbench import stats                            # noqa: E402
from chipbench.flops import Dims                       # noqa: E402
from chipbench.traffic import Generator, check_sample  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SECONDS = 10.0     # traced part of the window in a --trace 1 run
GRACE_SECONDS = 60.0     # wait for requests in flight after the window
REQ_ID_BASE = 1 << 40    # ids of set-up requests, apart from the server's


class BenchError(RuntimeError):
    """A run that cannot produce a result (exit 2, no result line)."""


def log(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / confs[w["config"]]["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                mix=mix, limits=limits,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def metric_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# What the metric readers get
# ---------------------------------------------------------------------------
@dataclass
class RunData:
    """Everything a per-layer reader may read. Times are
    ``time.monotonic`` seconds; ``snap`` holds the program's counters at
    the window's start (``"w0"``) and end (``"w1"``)."""
    dims: Dims
    peak: object
    seconds: float
    w0: float
    w1: float
    records: list
    requests: Dict[int, object]      # server rid -> the engine's Request
    prompts: Dict[int, int]          # server rid -> prompt length
    snap: Dict[str, dict]
    trace: Optional[object] = None
    trace_t0: float = 0.0
    trace_t1: float = 0.0

    def window_records(self):
        return [r for r in self.records if self.w0 <= r.due < self.w1]

    def delta(self, *path) -> float:
        a, b = self.snap["w0"], self.snap["w1"]
        for p in path:
            a, b = a[p], b[p]
        return b - a

    def prefilled_in(self, t0: float, t1: float):
        """(computed, total) prompt tokens of each request whose first
        token arrived in ``[t0, t1)``."""
        out = []
        for r in self.records:
            if r.token_times and t0 <= r.token_times[0] < t1 and \
                    r.rid in self.requests:
                q = self.requests[r.rid]
                if q.prefill_tokens_total:
                    out.append((q.prefill_tokens_computed,
                                q.prefill_tokens_total))
        return out

    def decoded_in(self, t0: float, t1: float):
        """Context length of each decoded token (output index >= 1)
        received in ``[t0, t1)``: the prompt, the tokens before it, and
        itself."""
        out = []
        for r in self.records:
            n = self.prompts.get(r.rid)
            if n is None:
                continue
            for i, t in enumerate(r.token_times):
                if i and t0 <= t < t1:
                    out.append(n + i)
        return out


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------
def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory in the checkout. Every program is
    kept, however quick its compile, so that a run after the first
    compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts programs lowered (every jit cache miss, persistent-cache
    hits included) and programs compiled by the backend; while
    ``watch`` is on it also keeps the names JAX logs for them."""

    def __init__(self):
        import logging
        from jax import monitoring
        self.lowered = self.compiled = 0
        self.names: List[str] = []
        monitoring.register_event_duration_secs_listener(self._on)
        counter = self

        class _Keep(logging.Handler):
            def emit(self, record):
                counter.names.append(record.getMessage()[:400])
        self._handler = _Keep(level=logging.WARNING)

    def watch(self, on: bool):
        import logging
        import jax
        jax.config.update("jax_log_compiles", on)
        log_ = logging.getLogger("jax")
        if on:
            log_.addHandler(self._handler)
        else:
            log_.removeHandler(self._handler)

    def _on(self, name, _secs, **_kw):
        if name.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered += 1
        elif name.endswith("backend_compile_duration"):
            self.compiled += 1

    def read(self):
        return self.lowered, self.compiled


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(
            f"needs a TPU, but JAX found platform {devs[0].platform!r} "
            f"({devs[0].device_kind}); there is no CPU fallback")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found "
                         f"{len(devs)}")
    return devs[:n]


def program_config(c: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        pattern=("attn",), rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        param_dtype=c["precision"]["weights"],
        dtype=c["precision"]["activations"])


def engine_spec(c: dict, ssd_dir: str, **over):
    from repro.serving.api import EngineSpec, StoreSpec
    from repro.serving.scheduler import SchedulerConfig
    e = dict(c["engine"])
    e.update(over)
    store = e.pop("store", None) or StoreSpec(ssd_dir=ssd_dir,
                                              **c["store"])
    return EngineSpec(sched=SchedulerConfig(**c["scheduler"]), store=store,
                      **e)


def set_prefill_bucket(eng, bucket: int):
    """The executor pads active rows and prompt length to multiples of
    its ``bucket``; the configuration states the compile bucket."""
    if not hasattr(eng.executor, "bucket"):
        raise BenchError("the executor has no prefill 'bucket' to set")
    eng.executor.bucket = int(bucket)


def program_request(rid: int, req, arrival: float = 0.0):
    from repro.serving.request import Request
    return Request(rid=rid, system_tokens=req.system,
                   chunk_tokens=list(req.chunks),
                   question_tokens=req.question,
                   max_new_tokens=int(req.max_new), arrival_time=arrival,
                   session=int(req.session))


def add_spans(eng):
    """Host spans around the benchmark's calls into each layer, so that
    a trace can say what the host did while the device idled."""
    import jax

    def wrap(obj, attr, name):
        fn = getattr(obj, attr, None)
        if fn is None:
            return

        def spanned(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        setattr(obj, attr, spanned)

    wrap(eng, "step", "bench.engine.step")
    wrap(eng.executor, "process_batch", "bench.executor.prefill")
    if eng.store is not None:
        wrap(eng.store, "get_kv", "bench.store.get_kv")
        wrap(eng.store, "add_variant", "bench.store.add_variant")


def _sweep_lengths(mix: dict) -> tuple:
    kb, k = mix["kb"], mix["retrieval"]["k"]
    fixed = mix["system_len"] + mix["question_len"]
    return fixed + k * kb["len_min"], fixed + k * kb["len_max"]


def _prompt_of_length(rng, mix, vocab, total):
    """System, chunks and question of a fresh prompt ``total`` tokens
    long (chunk lengths on the mix's quantum)."""
    kb, k = mix["kb"], mix["retrieval"]["k"]
    q = kb["len_quantum"]
    room = total - mix["system_len"] - mix["question_len"]
    lens = [max(kb["len_min"], min(kb["len_max"], room // k // q * q))] * k
    lens[-1] = max(kb["len_min"], min(kb["len_max"],
                                      room - sum(lens[:-1])))
    sys_t = rng.integers(0, vocab, mix["system_len"]).astype(np.int32)
    chunks = [rng.integers(0, vocab, n).astype(np.int32) for n in lens]
    ques = rng.integers(0, vocab, mix["question_len"]).astype(np.int32)
    return sys_t, chunks, ques


def sweep_prefill_shapes(cfg, weights, c: dict, mix: dict, ssd_dir: str):
    """Compile every (active rows, prompt length) bucket pair the mix can
    reach, through the program's own executor path (prefill programs are
    shared by every engine of one model configuration): a fresh prompt
    per length bucket, once with every token computed, then again with
    its chunks cached and the recompute fraction set so that the active
    rows land in each smaller bucket."""
    from repro.serving.api import StoreSpec, build_engine
    b = int(c["compile"]["prefill_bucket"])
    lo, hi = _sweep_lengths(mix)
    side = build_engine(engine_spec(
        c, ssd_dir, pool_blocks=1,
        store=StoreSpec(ssd_dir=ssd_dir, hbm_bytes=1 << 34,
                        cpu_bytes=1 << 30, n_chunks=64, m_variants=4)),
        cfg=cfg, params=weights)
    set_prefill_bucket(side, b)
    ex = side.executor
    rng = np.random.default_rng(0)
    try:
        for s_b in range(b, -(-hi // b) * b + 1, b):
            total = min(hi, max(lo, s_b - b // 2))
            if not s_b - b < total <= s_b:
                continue
            parts = _prompt_of_length(rng, mix, cfg.vocab_size, total)
            ex.force_recompute_fraction = None
            ex.process(*parts)
            qn = mix["question_len"]
            for a_b in range(b, s_b, b):
                target = max(qn + 1, a_b - b // 2)
                ex.force_recompute_fraction = (target - qn) / (total - qn)
                ex.process(*parts)
    finally:
        if side.store is not None:
            side.store.tiers.close()
    del side, ex
    gc.collect()


def warm_pool_programs(cfg, c: dict, mix: dict, weights):
    """Compile the paged decode step for every bucket of block rows, and
    the pool's dirty-block upload for every bucket of dirty blocks, that
    the mix can reach: the program's own jitted functions, called with
    every row masked on a zero pool of the engine's shapes (made and
    dropped before the engine's own pool exists)."""
    import jax.numpy as jnp
    from repro.core.prefill import (pack_paged_cache, paged_decode_fn,
                                    paged_sync_fn)
    e = c["engine"]
    bs, nb = int(e["block_size"]), int(e["pool_blocks"])
    B, S = int(e["decode_bucket_b"]), int(e["seq_bucket"])
    L, hkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    lo, hi = _sweep_lengths(mix)
    segs = mix["retrieval"]["k"] + 2          # block-aligned segments
    most = -(-(hi + int(mix["output"]["max"]) + 1) // bs) + segs + 1
    least = -(-lo // bs)
    kz = np.zeros((L, nb, bs, hkv, dh), np.float32)
    cache = pack_paged_cache(cfg, kz, kz, np.full((nb, bs), -1, np.int32))
    del kz
    sync = paged_sync_fn(cfg)
    for m in range(8, -(-min(2 * most, nb) // 8) * 8 + 1, 8):
        z = np.zeros((L, m * bs, hkv, dh), np.float32)
        idx = np.full(m * bs, -1, np.int64)   # as the engine passes them
        cache = sync(cache, jnp.asarray(idx, jnp.int32), jnp.asarray(z),
                     jnp.asarray(z), jnp.asarray(idx, jnp.int32))
    step = paged_decode_fn(cfg, e["attn_impl"], bs)
    for nbm in range(-(-least // 8) * 8, -(-most // 8) * 8 + 1, 8):
        _logits, cache = step(
            weights, jnp.asarray(np.zeros(B, np.int32)),
            jnp.asarray(np.full(B, -1, np.int32)), cache,
            jnp.asarray(np.full(B, -1, np.int32)),
            jnp.asarray(np.full((B, S), -1, np.int32)),
            jnp.asarray(np.full((B, nbm), -1, np.int32)))
    del cache


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def _snapshot(eng) -> dict:
    d = eng.stats_dict()
    hits = {}
    tiers = getattr(eng.store, "tiers", None) if eng.store else None
    if tiers is not None:
        hits = dict(tiers.stats.get("hits", {}))
    d["tier_hits"] = hits
    return d


def _fmt(x) -> str:
    return repr(float(x))


@dataclass
class Served:
    """A cell's engine, built and warmed, behind its HTTP server."""
    devs: list
    peak: object
    dims: Dims
    cfg: object
    weights: object
    eng: object
    srv: object
    counter: CompileCounter
    ssd_dir: str
    captured: Dict[int, object] = field(default_factory=dict)

    def close(self):
        """Cancel what is still in flight, stop the server and the
        store's workers and drop the engine, so that its device memory
        is free; the weights stay."""
        if self.srv is not None:
            for rid, req in list(self.captured.items()):
                if not req.finished:
                    self.srv.cancel(rid)
            self.srv.shutdown()
        if self.eng is not None and self.eng.store is not None:
            self.eng.store.tiers.close()
        shutil.rmtree(self.ssd_dir, ignore_errors=True)
        self.srv = self.eng = None
        gc.collect()


def serve(cell: Cell, c: dict, mix: dict, gen: Generator, seed: int,
          root: Path, require_chip: bool, compile_cache: bool = True
          ) -> Served:
    """Set-up: weights, the shape sweeps, the engine behind its server,
    and the store warm-up."""
    if not (root / "src" / "repro").is_dir():
        raise BenchError(f"the program (src/repro) is not in {root}")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    import jax
    cache_dir = enable_compile_cache(root) if compile_cache else "off"
    devs = require_chips(cell.chips) if require_chip else \
        jax.devices()[:cell.chips]
    dev = devs[0]
    log(f"device: platform {dev.platform}, device_kind {dev.device_kind}, "
        f"count {len(devs)}; compile cache {cache_dir}")
    from chipbench.peaks import peak_for
    peak = peak_for(dev.device_kind) if require_chip else None
    counter = CompileCounter()
    from chipbench.weights import make_weights
    from repro.serving.api import build_engine
    from repro.serving.server import CacheCraftServer
    dims = Dims.from_config(c)
    cfg = program_config(c)
    ssd_dir = tempfile.mkdtemp(prefix="chipbench-store-")
    t = time.monotonic()
    weights = make_weights(dims, seed, c["precision"]["weights"])
    jax.block_until_ready(weights)
    log(f"weights: {sum(x.size for x in jax.tree.leaves(weights))} "
        f"parameters made on the device in {time.monotonic() - t:.2f} s")
    sv = Served(devs=devs, peak=peak, dims=dims, cfg=cfg, weights=weights,
                eng=None, srv=None, counter=counter, ssd_dir=ssd_dir)
    try:
        t = time.monotonic()
        sweep_prefill_shapes(cfg, weights, c, mix, ssd_dir)
        log(f"prefill shape sweep: {time.monotonic() - t:.2f} s")
        t = time.monotonic()
        warm_pool_programs(cfg, c, mix, weights)
        log(f"pool program sweep: {time.monotonic() - t:.2f} s")
        sv.eng = eng = build_engine(engine_spec(c, ssd_dir), cfg=cfg,
                                    params=weights)
        set_prefill_bucket(eng, c["compile"]["prefill_bucket"])
        t = time.monotonic()
        warm = [program_request(REQ_ID_BASE + i, r)
                for i, r in enumerate(gen.warmup())]
        ws = eng.run(warm)
        log(f"store warm-up: {len(warm)} requests in "
            f"{time.monotonic() - t:.2f} s, {ws.completed} completed, "
            f"{ws.failed} failed")
        add_spans(eng)
        submit = eng.submit

        def capture(req):
            sv.captured[req.rid] = req
            return submit(req)
        eng.submit = capture
        sv.srv = CacheCraftServer(eng).start()
    except BaseException:
        sv.close()
        raise
    return sv


@dataclass
class Window:
    records: list
    snap: Dict[str, dict]
    w0: float
    w1: float
    trace_dir: Optional[str] = None
    trace_t0: float = 0.0
    trace_t1: float = 0.0


def drive(sv: Served, reqs, mix: dict, seconds: float,
          trace: bool) -> Window:
    """The lead-in, then the measured window (traced at its start with
    ``trace``), then the wait for requests still in flight."""
    import jax
    from chipbench.client import ClosedLoop, OpenLoop
    arr = mix["arrival"]
    t_start = time.monotonic() + 0.5
    w0 = t_start + float(mix["lead_in_s"])
    w1 = w0 + seconds
    srv = sv.srv
    if arr["kind"] == "closed":
        loop = ClosedLoop(srv.host, srv.port, reqs, int(arr["clients"]),
                          stop_at=w1)
        while time.monotonic() < t_start:
            time.sleep(0.01)
        loop.start()
    else:
        loop = OpenLoop(srv.host, srv.port, reqs, w0).start()
    win = Window(records=[], snap={}, w0=w0, w1=w1)
    while time.monotonic() < w0:
        time.sleep(0.001)
    win.snap["w0"] = _snapshot(sv.eng)
    c0 = sv.counter.read()
    sv.counter.watch(True)
    if trace:
        win.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        opts.host_tracer_level = 1        # the benchmark's spans only
        jax.profiler.start_trace(win.trace_dir, profiler_options=opts)
        win.trace_t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            time.sleep(max(0.0, min(w1, win.trace_t0 + TRACE_SECONDS)
                           - time.monotonic()))
        win.trace_t1 = time.monotonic()
        jax.profiler.stop_trace()
    while time.monotonic() < w1:
        time.sleep(0.001)
    win.snap["w1"] = _snapshot(sv.eng)
    c1 = sv.counter.read()
    sv.counter.watch(False)
    loop.finish(time.monotonic() + GRACE_SECONDS)
    win.records = loop.records
    cn = sv.eng.counters
    retries = getattr(sv.eng.scheduler, "retries", {})
    log(f"engine: {cn.burn_requeues} write-back requeues, "
        f"{cn.preemptions} preemptions, {sum(retries.values())} retries "
        f"of {len(retries)} requests, {cn.reserve_failures} deferred "
        f"admissions")
    log(f"compiles in the window: {c1[0] - c0[0]} programs lowered, "
        f"{c1[1] - c0[1]} compiled by the backend")
    for name in [n for n in sv.counter.names
                 if n.startswith("Compiling")][:20]:
        log(f"  compiled in the window: {name}")
    sv.counter.names.clear()
    return win


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, root: Path = ROOT,
             config: Optional[dict] = None, mix: Optional[dict] = None,
             limits: Optional[dict] = None, control: bool = False,
             readings: Optional[dict] = None, compile_cache: bool = True,
             out=None) -> dict:
    """One run of one cell; returns the result object (also printed as
    the last line of ``out``). ``config``/``mix``/``limits`` replace the
    cell's files (tests run tiny sizes on the CPU with
    ``require_chip=False`` and ``compile_cache=False``). With
    ``control`` the output check judges the control in the program's
    place, by the same limits, and ``correct`` is the control's; the
    program's own check of the same run goes into ``readings``
    (``readings["program"]``) where it is given."""
    out = out or sys.stdout
    cell = load_cell(workload, root)
    c = config or cell.config
    mix = mix or cell.mix
    limits = limits or cell.limits
    gen = Generator(mix, int(c["vocab_size"]), seed)
    reqs = gen.window(seconds)
    sv = serve(cell, c, mix, gen, seed, root, require_chip, compile_cache)
    try:
        win = drive(sv, reqs, mix, seconds, trace)
        setup_s = win.w0 - T_PROCESS
        mem = [d.memory_stats() or {} for d in sv.devs]
        peak_bytes = max((m.get("peak_bytes_in_use", 0) for m in mem),
                         default=0)
    finally:
        sv.close()
    by_idx = {r.idx: r for r in reqs}
    prompts = {rec.rid: len(by_idx[rec.idx].prompt)
               for rec in win.records if rec.rid >= 0}
    run = RunData(dims=sv.dims, peak=sv.peak, seconds=seconds, w0=win.w0,
                  w1=win.w1, records=win.records, requests=sv.captured,
                  prompts=prompts, snap=win.snap, trace_t0=win.trace_t0,
                  trace_t1=win.trace_t1)
    dev = sv.devs[0]
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(sv.devs),
                     "memory_peak_bytes": int(peak_bytes)}
    breakdown = None
    if trace:
        from chipbench import trace as T
        path = T.find_xplane(win.trace_dir)
        if path is None:
            raise BenchError("the profiler wrote no trace")
        run.trace = T.read(path, window_s=win.trace_t1 - win.trace_t0)
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        result_device["busy_s"] = run.trace.busy_s()
        result_device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": [[n[:160], t]
                                    for n, t in run.trace.top_ops()],
                     "idle_gaps": [list(x) for x in run.trace.idle_gaps()]}
        for name, secs, text in T.describe(run.trace, 30):
            log(f"  device op {secs:.6f} s {name}: {text[:300]}")

    # ---- the output check --------------------------------------------------
    win_recs = run.window_records()
    attempted = len(win_recs)
    failed = sum(1 for r in win_recs if r.state != "done")
    check, ccheck, extra = output_check(run, reqs, sv.weights, sv.dims, c,
                                        mix, limits, seed, control)
    if control:
        for k, v in check.items():
            log(f"program check {k}: {_fmt(v['value'])} limit "
                f"{_fmt(v['limit'])}")
        if readings is not None:
            readings["program"] = check
        check = ccheck
    correct = passes(check) and extra["compared"] > 0

    # ---- metrics ----------------------------------------------------------
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            v = end_to_end(m["name"], run, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"requests: {attempted} due in the window, {failed} failed; "
        f"output check over {extra['compared']} requests, "
        f"{extra['tokens']} served tokens")
    if control:
        log("control (fp8 reference in the program's place): the check "
            "below is the control's")
    for k, v in check.items():
        print(f"check {k}: {_fmt(v['value'])} limit {_fmt(v['limit'])}",
              file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = check
    print(json.dumps(result), file=out, flush=True)
    return result


def end_to_end(name: str, run: RunData, setup_s: float) -> Optional[float]:
    win = run.window_records()
    if name == "setup_s":
        return setup_s
    if name == "output_tok_s":
        return stats.rate(stats.tokens_in(run.records, run.w0, run.w1),
                          run.seconds)
    if name.startswith("ttft_p"):
        q = float(name[len("ttft_p"):].split("_")[0])
        xs = stats.ttft_samples(win, run.w0, run.w1)
        return stats.percentile(xs, q) * 1e3 if xs else None
    if name.startswith("itl_p"):
        q = float(name[len("itl_p"):].split("_")[0])
        xs = stats.itl_samples(win, run.w0, run.w1)
        return stats.percentile(xs, q) * 1e3 if xs else None
    raise BenchError(f"no end-to-end metric {name!r}")


def passes(check: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in check.values())


def output_check(run: RunData, reqs, weights, dims, c, mix, limits, seed,
                 control: bool):
    """Every finished request of the window: its stream carried exactly
    the tokens the engine emitted, and as many as it asked for. A sample
    of the finished requests, drawn from the seed with the longest among
    them: the widest and the mean gap by which a served token's logit
    lies below the plain reference's best. Returns the program's check,
    the control's (``None`` without ``control``): the same numbers read
    from the reference at fp8 in the program's place, at the same
    positions, against the same limits; and what was compared."""
    from chipbench import reference as R
    by_idx = {r.idx: r for r in reqs}
    win = run.window_records()
    done = [r for r in win if r.state == "done"]
    mismatch = short = 0
    for r in done:
        q = run.requests.get(r.rid)
        if q is None or list(q.output_tokens) != r.tokens:
            mismatch += 1
        if len(r.tokens) != by_idx[r.idx].max_new:
            short += 1
    lengths = {r.idx: len(by_idx[r.idx].prompt) + len(r.tokens)
               for r in done}
    sample = check_sample([r.idx for r in done if r.tokens], lengths,
                          int(mix["check_requests"]), seed)
    recs = {r.idx: r for r in done}
    theta, eps = float(c["rope_theta"]), float(c["rms_norm_eps"])
    gaps, cgaps = [], []
    for i in sample:
        prompt, served = by_idx[i].prompt, recs[i].tokens
        g, ref = R.served_gaps(weights, dims, theta, eps, prompt, served)
        gaps.append(g)
        if control:
            seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
            rows = list(range(len(prompt) - 1,
                              len(prompt) - 1 + len(served)))
            low = R.logits_at(weights, dims, theta, eps, seq, rows,
                              control=True)
            cgaps.append(R.control_gaps(ref, low))
    log(f"served tokens not the reference's best: {R.flips(gaps)} of "
        f"{sum(len(g) for g in gaps)}; mean gap {_fmt(R.mean(gaps))}")
    if control:
        log(f"control: tokens not the reference's best {R.flips(cgaps)}; "
            f"mean gap {_fmt(R.mean(cgaps))}")

    def judged(g):
        return {
            "gap_max": {"value": R.widest(g), "limit": limits["gap_max"]},
            "gap_mean": {"value": R.mean(g), "limit": limits["gap_mean"]},
            "stream_mismatch": {"value": float(mismatch), "limit": 0.0},
            "short_streams": {"value": float(short), "limit": 0.0},
        }
    extra = {"compared": len(sample),
             "tokens": int(sum(len(g) for g in gaps))}
    return judged(gaps), judged(cgaps) if control else None, extra


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
