"""End-to-end serving engine: continuous batching + Cache-Craft prefill.

Timing model: compute is *measured* on this host (jitted model steps);
the engine clock advances by measured compute plus the *modeled* tier
load costs that are not hidden by queue wait (paper §3.5: async preload
overlaps loading with queue time; layer-wise preload (Eq. 16) overlaps
the rest with layer execution). This gives reproducible throughput /
latency curves at laptop scale with the same structure as the paper's
A100 numbers.

KV accounting is reservation-based: the scheduler reserves every
admitted request's blocks up front (``KVPool.reserve``), prefill writes
and decode appends draw from the reservation, and terminal states
commit (success) or cancel (requeue/failure) it — so a request can
never burn its share of the packed prefill pass and then fail
``write_prefill`` (``counters.burn_requeues`` stays 0).

Incremental decode batch (row-masking scheme): the jitted decode cache
is a bucketed (B, S) arena with a request-per-row map. Joins write the
new request's gathered KV into a free row in place; leaves mask the row
(cache position row set to -1, per-step query position/slot -1, see
``core.prefill.decode_fn``) and recycle it for the next join. A full
gather rebuild happens only when the bucketed (B, S) shape must grow,
cutting per-iteration overhead under churny workloads.

Zero-copy chunk sharing (``share_chunk_kv``, on by default with a
store): instead of copying every hit chunk's KV into private pool
blocks per request, the write-back assembles the block table segment by
segment — hit chunks attach the store's canonical pool-resident run via
``KVPool.append_shared`` (refcount bump, nothing copied), recompute
fixup rows CoW into the request's table, and only miss/question
segments allocate fresh blocks. Admission then reserves only the delta
blocks (``_estimate_blocks``), so N concurrent requests over the same
hot chunk pay ~1x its HBM instead of Nx and more requests pack per
iteration under pool pressure.

Reservation-aware preemption (preempt lifecycle): admission only
*defers* a queue head that cannot reserve, so a fully-reserved decode
batch under sustained shortage would starve it indefinitely — zero-copy
sharing makes resident blocks cheaper but shortage *stickier* (shared
runs and deep reservations pin the pool). When the head has failed to
reserve for ``SchedulerConfig.preempt_after_iters`` consecutive
iterations and the cold-run reclaim found nothing to free, ``step``
preempts scheduler-selected victims (newest decode requests first,
one at a time until the head's retried admission succeeds): per
victim, ``_preempt`` masks its decode row (``_decode_leave``),
releases its shared-run reader refs (``_release_runs``), frees its
block table and cancels its reservation in one pool op
(``KVPool.reclaim_request``), and resets its attempt state
(``Request.reset_attempt``, with ``reserve_full`` cleared — re-entry
is a normal prefill that re-uses any shared runs it just released,
which stay pool-resident at zero readers). Admission is retried *in
the same iteration* so the starved head — not a victim — takes the
freed blocks, and only afterwards are the victims requeued at the
queue *front* (``Scheduler.preempt_requeue``), preserving their FCFS
priority over the rest of the queue; freed blocks therefore
accumulate across victims until they cover the head's shortfall
instead of being re-reserved by the victim one iteration later. Preemptions are counted separately from retries, so
``retry_limit`` still bounds genuine failures; ``preempt_limit`` caps
per-request victimhood for liveness. The same teardown
(``_teardown``) also serves the straggler guard: queued requests whose
wait exceeds ``SchedulerConfig.deadline_s`` FAIL at the top of
``step`` instead of deadlocking the queue.

Cache-manager integration (§3.5 tentpole): tier prefetch is
queue-driven — every iteration, ``_prefetch_lookahead`` issues
promotions for the first ``SchedulerConfig.prefetch_lookahead`` queued
requests under a cancellable ``PrefetchTicket`` (teardown retracts
pending promotions; counters ``prefetch_issued``/``prefetch_cancels``).
With ``layerwise_load=True`` the prefill executor streams hit-chunk KV
layer by layer (Eq. 16 / ``core.preload.LayerStream``): the pass
starts once the first ``preload_depth`` layers are resident and the
engine's ``load_exposed_s``/``load_hidden_s`` become *measured*
await-point overlap instead of the modeled formula (the eager path
keeps the formula). Victim selection everywhere (tier demotion,
variant capping, pool-run reclaim) goes through one
``core.eviction.EvictionPolicy``.

Online serving (serving.server / serving.api): engines are constructed
through the typed ``EngineSpec``/``build_engine`` front door (the old
untyped executor-kwargs dict survives one release as a deprecated
alias that folds into the typed fields). The decode loop feeds a
per-token event buffer (``drain_tokens``) so a server can stream
tokens as they are produced, and ``request_cancel``/``cancel`` tear a
request down mid-flight — mid-queue (prefetch ticket retracted) or
mid-decode (row masked, shared-run readers released, blocks +
reservation reclaimed) — through the same ``_teardown`` path the
preemption and expiry guards use, so pool conservation holds. The
batch-replay ``run`` and the server's live loop share one
``step_until_idle`` stepping/clock-advance policy.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chunkstore import ChunkStore, prompt_hashes
from repro.core.prefill import CacheCraftExecutor, inject_chunk_kv, \
    pack_cache
from repro.core.preload import preload_depth
from repro.core.tiers import PrefetchTicket
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving.kvpool import KVPool
from repro.serving.metrics import ServingCounters
from repro.serving.request import Request, State
from repro.serving.scheduler import Scheduler, SchedulerConfig


# prefill-window backend of each decode-only (paged) route
_PREFILL_IMPL = {"paged": "dense", "paged_kernel": "kernel"}


def _bucket(n: int, b: int) -> int:
    return max(b, -(-n // b) * b)


@functools.lru_cache(maxsize=None)
def _join_row_fn(cfg):
    """Jitted in-place decode-batch join: write one request's gathered
    KV [L, S, Hkv, D] (+ pos [S]) into batch row ``row`` of the decode
    cache. One fused call (cache donated, so XLA can alias the buffers
    where the backend supports it) instead of 3 * (P + n_tail) separate
    whole-cache copies."""
    P, G = len(cfg.pattern), cfg.n_groups

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fn(cache, row, k, v, pos):
        out = {"groups": [], "tail": []}
        if G:
            kg = k[:G * P].reshape((G, P) + k.shape[1:])
            vg = v[:G * P].reshape((G, P) + v.shape[1:])
            for p in range(P):
                c = cache["groups"][p]
                out["groups"].append({
                    "k": c["k"].at[:, row].set(kg[:, p]),
                    "v": c["v"].at[:, row].set(vg[:, p]),
                    "pos": c["pos"].at[:, row].set(pos),
                })
        for i in range(cfg.n_tail):
            t = cache["tail"][i]
            out["tail"].append({
                "k": t["k"].at[row].set(k[G * P + i]),
                "v": t["v"].at[row].set(v[G * P + i]),
                "pos": t["pos"].at[row].set(pos),
            })
        return out
    return fn


@functools.lru_cache(maxsize=None)
def _leave_row_fn(cfg):
    """Jitted in-place decode-batch leave: mask batch row ``row`` by
    setting its position row to -1 (KV left in place — the position
    mask makes the row inert, and the next join overwrites it)."""
    G = cfg.n_groups

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fn(cache, row):
        out = {"groups": [], "tail": []}
        if G:
            for c in cache["groups"]:
                out["groups"].append({
                    "k": c["k"], "v": c["v"],
                    "pos": c["pos"].at[:, row].set(-1),
                })
        for t in cache["tail"]:
            out["tail"].append({
                "k": t["k"], "v": t["v"],
                "pos": t["pos"].at[row].set(-1),
            })
        return out
    return fn


@dataclass
class EngineStats:
    prefill_tokens_total: int = 0
    prefill_tokens_computed: int = 0
    decode_steps: int = 0
    prefills: int = 0
    prefill_batches: int = 0            # packed prefill passes executed
    prefill_batch_max: int = 0          # most prefills admitted in one pass
    completed: int = 0
    failed: int = 0
    cancelled: int = 0                  # user-cancelled (Engine.cancel)
    clock: float = 0.0
    load_hidden_s: float = 0.0
    load_exposed_s: float = 0.0
    # quantized-tier capacity effect (core.tiers "Quantized tiers"):
    # raw-minus-stored bytes across every demotion encode, and how many
    # tier reads paid a dequant on the worker lanes
    tier_quant_bytes_saved: int = 0
    tier_dequant_loads: int = 0

    def stats_dict(self) -> dict:
        """The one exported engine-stats payload (field name -> value).
        Shares its schema duty with ``ServingCounters.stats_dict`` —
        the server's ``/stats`` endpoint and the benches consume these
        instead of hand-picking attributes."""
        return dataclasses.asdict(self)


class Engine:
    def __init__(self, cfg: ModelConfig, params,
                 store: Optional[ChunkStore] = None, *,
                 sched: Optional[SchedulerConfig] = None,
                 pool_blocks: int = 4096, block_size: int = 16,
                 decode_bucket_b: int = 4, seq_bucket: int = 64,
                 strategy: str = "cachecraft",
                 use_focus: bool = True,
                 force_recompute_fraction: Optional[float] = None,
                 layerwise_load: bool = False,
                 store_fixed_variants: bool = True,
                 store_new_chunks: bool = True,
                 fix_rpe: bool = True, fix_causality: bool = True,
                 executor_kwargs: Optional[dict] = None,
                 time_scale: float = 1.0,
                 incremental_decode: bool = True,
                 share_chunk_kv: bool = True,
                 trace_decode: bool = False,
                 attn_impl: Optional[str] = None,
                 paged_decode: bool = False,
                 mesh=None):
        self.cfg = cfg
        self.store = store
        # attention backend selection (models.backend.BACKENDS). None
        # keeps the legacy split: "dense" prefill windows, "auto"
        # decode. A serving mesh forces the "sharded" backend and a
        # matching head-sharded pool layout; the mesh must be installed
        # before the first trace of any jit root that runs under it.
        # Params are replicated onto the mesh, so every shard reads its
        # own copy instead of pulling from wherever init left them.
        self.mesh = mesh
        kv_shards = 1
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.distributed.sharding import serving_kv_shards
            from repro.models import backend as AB
            kv_shards = serving_kv_shards(mesh, cfg)
            AB.set_serving_mesh(mesh)
            params = jax.device_put(
                params, NamedSharding(mesh, PartitionSpec()))
            # the paged route keeps its kernel under the mesh (the
            # backend runs it per shard); everything else goes sharded
            if not (paged_decode and attn_impl == "paged_kernel"):
                attn_impl = "sharded"
        self.params = params
        self.attn_impl = attn_impl
        self.kv_shards = kv_shards
        # typed executor construction (serving.api.EngineSpec is the
        # front door). ``executor_kwargs`` is a deprecated alias kept
        # one release: the dict folds over the typed fields so old call
        # sites keep working, with a warning pointing at the spec.
        ek = dict(strategy=strategy, use_focus=use_focus,
                  force_recompute_fraction=force_recompute_fraction,
                  layerwise_load=layerwise_load,
                  store_fixed_variants=store_fixed_variants,
                  store_new_chunks=store_new_chunks,
                  fix_rpe=fix_rpe, fix_causality=fix_causality)
        if executor_kwargs:
            warnings.warn(
                "Engine(executor_kwargs=...) is deprecated; construct "
                "engines through serving.api.EngineSpec/build_engine "
                "(or the Engine keyword arguments it forwards)",
                DeprecationWarning, stacklevel=2)
            ek.update(executor_kwargs)
        if attn_impl is not None:
            # the paged routes only change decode: their prefill windows
            # run the backend each reduces to (sharded under a mesh)
            ek.setdefault("attn_impl", "sharded" if mesh is not None
                          else _PREFILL_IMPL.get(attn_impl, attn_impl))
        self.executor = CacheCraftExecutor(cfg, params, store, **ek)
        self.scheduler = Scheduler(sched or SchedulerConfig())
        self.counters = ServingCounters()
        self.pool = KVPool(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_,
                           pool_blocks, block_size, counters=self.counters,
                           kv_shards=kv_shards)
        # zero-copy chunk sharing needs a store AND layout-local
        # positions (fix_rpe/fix_causality), otherwise the injected KV
        # is not a function of (variant, layout start) alone; a
        # recompute fraction of 1.0 rewrites every cached row, leaving
        # nothing shareable (the write-back would pin runs only to CoW
        # every block, and the delta estimate would under-reserve)
        frac = self.executor.force_recompute_fraction
        self.share_chunk_kv = bool(
            share_chunk_kv and store is not None
            and self.executor.fix_rpe and self.executor.fix_causality
            and (frac is None or frac < 1.0))
        if self.share_chunk_kv:
            store.attach_pool(self.pool)
        self.decode_bucket_b = decode_bucket_b
        self.seq_bucket = seq_bucket
        self.time_scale = time_scale
        self.incremental_decode = incremental_decode
        self.clock = 0.0
        self.decoding: List[Request] = []
        self._dcache = None
        self._dshape = None
        self._rows: List[Optional[Request]] = []   # batch row -> request
        self._masked_rows: set = set()             # rows freed by a leave
        self._needs_rebuild = True
        self.stats = EngineStats()
        # test/bench support: per-step decode logits and final pool KV
        self.trace_decode = trace_decode
        self.decode_trace: List[Dict[int, np.ndarray]] = []
        self.final_kv: Dict[int, tuple] = {}
        # online serving support. Token events: every token the decode
        # loop (or the prefill's first-token argmax) produces is
        # appended as (rid, token) and drained by ``drain_tokens`` —
        # the server's engine-loop thread routes them into per-request
        # stream queues. Cancellation: HTTP threads only *request* a
        # cancel (``request_cancel``); the engine thread applies it at
        # the top of the next ``step`` so all jax/pool state stays
        # single-threaded.
        self._token_events: List[Tuple[int, int]] = []
        self._events_lock = threading.Lock()
        self._cancel_pending: set = set()
        from repro.core.prefill import decode_fn
        self._decode_fn = decode_fn(cfg, self.attn_impl or "auto")
        # paged decode (block-table-native attention): the decode pass
        # reads K/V in place from a device twin of the pool's block
        # arenas, indexed per request by compact slot rows — joins and
        # leaves become row-map updates, rebuilds only re-bucket the
        # index tensor, and the new token's KV is scattered into its
        # pre-opened pool slot inside the jitted pass. The twin stays
        # coherent by uploading the pool's dirty-block log before each
        # step (counted: paged_block_syncs / paged_sync_bytes), while
        # the arena path's per-request copies land in
        # decode_gather_bytes / decode_join_copies — ~0 here.
        self.paged_decode = bool(paged_decode)
        self._pcache = None
        self._paged_kernel = bool(paged_decode) and \
            self.attn_impl in ("paged_kernel",)
        if paged_decode:
            from repro.core.prefill import paged_decode_fn, paged_sync_fn
            impl = self.attn_impl \
                if self.attn_impl in ("paged", "paged_kernel") else "paged"
            self._paged_fn = paged_decode_fn(cfg, impl, block_size)
            self._psync = paged_sync_fn(cfg)

    # ---- submission ---------------------------------------------------------
    def submit(self, req: Request):
        self.clock = max(self.clock, req.arrival_time)
        self.scheduler.enqueue(req, self.clock)
        # async preload (§3.5) is queue-driven now: ``step`` issues tier
        # promotions for the scheduler's look-ahead window each
        # iteration (``_prefetch_lookahead``) instead of for every
        # request at enqueue time — deep-queue requests no longer flush
        # the HBM tier hours before they could possibly run.

    def _prefetch_lookahead(self):
        """Issue tier promotions for queued requests entering the
        scheduler's look-ahead window, each under a cancellable ticket
        so teardown (expiry/preemption/requeue) can retract promotions
        that have not been served yet."""
        if self.store is None:
            return
        for req in self.scheduler.prefetch_targets():
            if req.prompt_hashes is None:
                req.prompt_hashes = prompt_hashes(req.system_tokens,
                                                  req.chunk_tokens)
            req.prefetch_ticket = PrefetchTicket()
            for i, h in enumerate(req.prompt_hashes):
                self.store.prefetch(h, req.prompt_hashes[:i],
                                    ticket=req.prefetch_ticket)
            self.counters.prefetch_issued += 1

    # ---- per-token streaming ------------------------------------------------
    def _emit_token(self, req: Request, token: int):
        """Queue one (rid, token) event for ``drain_tokens``, at most
        once per output index: ``Request.tokens_emitted`` survives
        ``reset_attempt``, so when a requeue/preemption burns an
        attempt whose tokens were already fanned out to a live stream,
        the retry recomputes the same prefix (decode is deterministic
        per request) but re-emits nothing — the stream sees each index
        exactly once."""
        n = len(req.output_tokens)
        if n <= req.tokens_emitted:
            return
        req.tokens_emitted = n
        with self._events_lock:
            self._token_events.append((req.rid, token))

    def drain_tokens(self) -> List[Tuple[int, int]]:
        """Drain the per-token event buffer: every (rid, token) pair
        produced since the last drain, in production order. The decode
        loop (and the prefill first-token argmax) feed it; the online
        server drains after each step and fans the events out to the
        per-request HTTP streams. Thread-safe (a buffer swap under a
        lock), so a non-engine thread may drain — but the ownership
        contract (serving.server) keeps it on the engine loop."""
        with self._events_lock:
            out = self._token_events
            self._token_events = []
        return out

    # ---- cancellation -------------------------------------------------------
    def request_cancel(self, rid: int):
        """Thread-safe cancellation request: mark ``rid`` for cancel and
        return immediately. The engine thread applies it at the top of
        its next ``step`` (``cancel``), so HTTP handler threads never
        touch jax or pool state."""
        self._cancel_pending.add(rid)

    def _process_cancels(self) -> bool:
        if not self._cancel_pending:
            return False
        worked = False
        while self._cancel_pending:
            worked |= self.cancel(self._cancel_pending.pop())
        return worked

    def cancel(self, rid: int) -> bool:
        """Cancel one request mid-flight, wherever it currently is:

        * still queued — removed from the scheduler queue (pending tier
          promotions retracted via its ``PrefetchTicket``);
        * mid-decode — its batch row is masked (``_decode_leave``), its
          shared-run reader refs released, and its table blocks plus
          open reservation reclaimed in one compound pool op.

        Both arms share ``_teardown`` with the preemption / expiry /
        requeue paths, so pool conservation
        (``free + live + reserved == num_blocks``) holds mid-decode by
        the same construction those paths are property-tested under.
        Returns False when ``rid`` is unknown or already terminal
        (cancelling a finished request is a no-op, not an error)."""
        for r in self.scheduler.queue:
            if r.rid == rid:
                self.scheduler.queue.remove(r)
                self._finish_cancel(r)
                return True
        for r in self.decoding:
            if r.rid == rid:
                row = next((i for i, q in enumerate(self._rows)
                            if q is r), None)
                self.decoding.remove(r)
                if row is not None:
                    self._decode_leave(row)
                else:
                    # admitted while a rebuild was pending: membership
                    # changed under the stale cache (same edge as
                    # ``_preempt``)
                    self._needs_rebuild = True
                self._finish_cancel(r)
                return True
        return False

    def _finish_cancel(self, req: Request):
        self._teardown(req)
        req.state = State.CANCELLED
        self.stats.cancelled += 1
        self.scheduler.on_terminal(req)

    # ---- one ORCA iteration -------------------------------------------------
    def step(self) -> bool:
        """Returns True if any work was done."""
        worked = self._process_cancels()
        worked = self._expire_queued() or worked
        self._prefetch_lookahead()
        fails_before = self.counters.reserve_failures
        reqs = self._admit()
        if not reqs and self.scheduler.queue \
                and self.counters.reserve_failures > fails_before:
            # head-of-line reservation failure this iteration. An
            # ORCA-budget or decode-cap deferral (reqs empty, no
            # reserve failure) skips this whole branch: it neither
            # counts toward the stall (nor resets it — budget churn
            # must not defeat preemption) nor triggers reclaim —
            # decode progress resolves those on its own
            head = self.scheduler.queue[0]
            reclaimed = False
            if self.share_chunk_kv:
                # admission backpressure: cold canonical runs (zero
                # readers) must not pin the pool while the queue
                # starves. Sized by the head's DELTA shortfall — even
                # with sharing the head could not reserve, so any cold
                # run freed helps.
                need = self._estimate_blocks(head)
                if self.pool.free_blocks < need:
                    if self.store.reclaim_pool_runs(
                            need - self.pool.free_blocks):
                        reclaimed = worked = True
            stall = self.scheduler.note_head_stall(head.rid)
            self.counters.head_stall_iters_max = max(
                self.counters.head_stall_iters_max, stall)
            if not reclaimed:
                victims: List[Request] = []
                if self.scheduler.should_preempt():
                    # preempt newest-first, retrying admission after
                    # each victim, until the starved head admits or
                    # eligible victims run out. Victims are requeued
                    # only AFTER the head's retry: requeued at the
                    # front they would be the new head and re-reserve
                    # their own freed blocks, burning a prefill per
                    # cycle without unblocking anyone — held back, the
                    # freed blocks accumulate until they cover the
                    # head's shortfall
                    while not reqs:
                        victim = self.scheduler.select_victim(
                            self.decoding)
                        if victim is None:
                            break
                        self._preempt(victim)
                        victims.append(victim)
                        reqs = self._admit()
                if victims:
                    # newest-first preemption order means appendleft
                    # restores FCFS: the oldest victim ends up at the
                    # queue front, ahead of everything still waiting
                    for victim in victims:
                        self.scheduler.preempt_requeue(victim)
                    worked = True
                elif not self._shortage_recoverable():
                    # shortage valve: nothing in flight will free
                    # blocks, nothing is reclaimable or preemptable,
                    # yet the head fits the pool in principle — burn a
                    # bounded retry so persistent shortage (e.g.
                    # leaked blocks) converges to FAILED, not a
                    # livelock
                    self.scheduler.requeue(self.scheduler.queue.popleft())
                    worked = True
        elif reqs:
            self.scheduler.note_head_progress()
        if reqs:
            self._run_prefills(reqs)
            worked = True
        if self.decoding:
            self._run_decode_step()
            worked = True
        return worked

    def _admit(self) -> List[Request]:
        return self.scheduler.next_prefills(
            sum(r.total_len for r in self.decoding), len(self.decoding),
            pool=self.pool,
            reserve_blocks_fn=self._estimate_blocks
            if self.share_chunk_kv else None)

    def _shortage_recoverable(self) -> bool:
        """Can blocks still come back without failing anyone? Decode
        completions free tables (and make preemption possible), and
        pool-resident runs at zero readers are reclaimable the moment
        admission pressure asks for them. Only when neither source
        exists is a reservation shortage terminal — that is when the
        shortage valve in ``step`` may burn a bounded retry."""
        if self.decoding:
            return True
        if self.share_chunk_kv and self.store.residency is not None:
            return any(r.readers <= 0 and not r.evict_pending
                       for r in self.store.residency.runs.values())
        return False

    def _expire_queued(self) -> bool:
        """Straggler guard (``SchedulerConfig.deadline_s``): FAIL queued
        requests whose wait exceeded the deadline, with full teardown —
        this used to be dead code (``Scheduler.expired`` had no caller),
        so the documented guard never fired."""
        sched = self.scheduler
        if not sched.queue:
            return False
        if sched.cfg.deadline_s <= 0 and \
                not any(r.deadline_s > 0 for r in sched.queue):
            return False
        expired = [r for r in sched.queue if sched.expired(r, self.clock)]
        for r in expired:
            sched.queue.remove(r)
            self._teardown(r)
            r.state = State.FAILED
            r.deadline_hit = True
            self.counters.deadline_expired += 1
            sched.on_terminal(r)
        return bool(expired)

    def _count_attn_flops(self, tq: int, tk: int):
        """Analytic attention FLOPs for one jitted pass (score + PV
        einsums over all layers, 4*Tq*Tk*H*D each): count-based so the
        sharded CI gate is timing-immune. The head axis partitions the
        einsums exactly, so the per-device share divides by the
        head-shard count."""
        f = 4 * tq * tk * self.cfg.num_heads * self.cfg.head_dim_ \
            * self.cfg.num_layers
        self.counters.attn_flops_total += f
        self.counters.attn_flops_device += f // self.kv_shards

    def _run_prefills(self, reqs: Sequence[Request]):
        """Packed multi-request prefill: every admitted request's
        recompute tokens execute as one jitted windowed pass. Admission
        reserved each request's KV blocks, so the write-back below
        cannot fail under pool pressure."""
        for req in reqs:
            req.state = State.PREFILLING
            req.t_prefill_start = self.clock
            if req.t_first_service is None:
                req.t_first_service = self.clock
        t0 = time.perf_counter()
        results = self.executor.process_batch(
            [(r.system_tokens, r.chunk_tokens, r.question_tokens)
             for r in reqs])
        compute_s = (time.perf_counter() - t0) * self.time_scale
        # tier loads. Streamed passes (layerwise_load executors) measure
        # the overlap for real: the pass's wall time already contains
        # exactly the *exposed* load seconds (per-layer await points
        # that actually blocked), while hidden layers loaded on the
        # background worker under earlier windows' compute — so the
        # clock advances by compute_s alone and the hidden/exposed
        # split is the executor's measurement, not a formula. Eager
        # passes keep the modeled account: queue wait hides loading
        # (async preload), layer-wise preload (Eq. 16) hides the
        # remainder behind layer compute. Requests packed into one pass
        # load their tiers concurrently, so the pass is delayed by the
        # worst per-request exposure, not the sum; hidden/exposed
        # totals still account every request.
        exposed_max = 0.0
        for req, res in zip(reqs, results):
            if res.streamed:
                exposed = res.load_exposed_measured * self.time_scale
                self.stats.load_exposed_s += exposed
                # hidden time is bounded by the loads' wall-clock span:
                # with parallel tier workers the per-load sum
                # (load_seconds_measured) overstates elapsed time
                self.stats.load_hidden_s += max(
                    0.0, min(res.load_seconds_measured,
                             res.load_span_measured) * self.time_scale
                    - exposed)
                self.counters.preload_layers_blocked += \
                    res.load_blocked_layers
                self.counters.preload_layers_hidden += \
                    res.load_hidden_layers
                continue
            t_enq = req.t_enqueued if req.t_enqueued is not None \
                else self.clock
            queue_wait = self.clock - t_enq
            lp = preload_depth(self.cfg.num_layers,
                               compute_s / max(1, self.cfg.num_layers),
                               res.load_seconds_modeled /
                               max(1, self.cfg.num_layers))
            exposed = max(0.0, res.load_seconds_modeled *
                          (lp / self.cfg.num_layers) - queue_wait)
            self.stats.load_hidden_s += res.load_seconds_modeled - exposed
            self.stats.load_exposed_s += exposed
            exposed_max = max(exposed_max, exposed)
        self.clock += compute_s + exposed_max
        self.stats.prefill_batches += 1
        self.stats.prefill_batch_max = max(self.stats.prefill_batch_max,
                                           len(reqs))

        joined: List[Request] = []
        for req, res in zip(reqs, results):
            ok = self._write_back(req, res)
            if not ok:
                # copy path: unreachable with reserve-at-admission
                # (counted so tests can assert 0). Zero-copy path: the
                # delta estimate does not budget CoW clones, so a tight
                # pool can fail the write-back — escalate the retry to
                # a full reservation + copy-style write-back, which the
                # reservation then covers by construction.
                self.counters.burn_requeues += 1
                req.reserve_full = True
                self._requeue(req)
                continue
            first = int(np.argmax(res.logits_last[:self.cfg.vocab_size]))
            self._count_attn_flops(res.plan.num_active_tokens,
                                   res.total_len)
            req.output_tokens.append(first)
            self._emit_token(req, first)
            req.total_len = res.total_len
            req.t_first_token = self.clock
            req.prefill_tokens_total = res.total_len
            req.prefill_tokens_computed = res.plan.num_active_tokens
            req.cache_hits = sum(d.is_hit for d in res.plan.decisions)
            req.load_seconds_modeled = res.load_seconds_modeled
            req.state = State.DECODING
            self.stats.prefills += 1
            self.stats.prefill_tokens_total += res.total_len
            self.stats.prefill_tokens_computed += res.plan.num_active_tokens
            self.counters.delta_blocks_saved += req.delta_blocks_saved
            req.delta_blocks_saved = 0
            self.decoding.append(req)
            joined.append(req)
        self._decode_join_batch(joined)

    # ---- zero-copy chunk sharing -------------------------------------------
    def _run_loader(self, variant, start: int, length: int):
        """Loader for a canonical pool run: the variant's stored KV
        roped at the layout span via the same ``inject_chunk_kv``
        transform the executor's compute pass uses — byte-identity is
        the zero-copy bit-equality contract (fix_rpe/fix_causality)."""
        def load():
            # re-reads the variant (the compute pass promoted it to the
            # HBM tier moments earlier) and re-ropes it: a once-per-run
            # cost, accepted over retaining a second copy of every hit
            # segment's injected bytes in each PrefillResult
            kv, _info = self.store.get_kv(variant)
            if kv is None:
                return None
            span = np.arange(start, start + length, dtype=np.int32)
            k, v = inject_chunk_kv(self.cfg, kv, span)
            return k, v, span
        return load

    def _write_back(self, req: Request, res) -> bool:
        """Persist one prefill result into the request's block table.

        Copy mode: one dense ``write_prefill``. Zero-copy mode: segment
        by segment — hit chunks attach the store's canonical shared run
        (recompute-fixup rows CoW into this table), everything else
        (miss chunks, the question) gets fresh block-aligned segments.
        Non-recompute rows of a hit segment are never touched by the
        windowed pass, so shared-run bytes + per-request fixups
        reproduce the copy path's KV exactly."""
        pool, plan = self.pool, res.plan
        if not self.share_chunk_kv or req.reserve_full:
            return pool.write_prefill(req.table, res.k_layers,
                                      res.v_layers, res.pos_layout,
                                      reservation=req.reservation)
        table = req.table
        for d in plan.decisions:
            seg = d.seg
            if seg.length == 0:
                continue
            # a hit whose recompute set covers the whole segment would
            # pin the run and then CoW-clone every block — strictly
            # more work than a private copy, so fall through
            if d.is_hit and len(d.recompute_idx) < seg.length:
                run = self.store.pin_pool_run(
                    d.variant, seg.start,
                    self._run_loader(d.variant, seg.start, seg.length),
                    reservation=req.reservation)
                if run is not None:
                    base = pool.append_shared(table, run.blocks)
                    req.shared_runs.append(run)
                    self.counters.shared_seg_hits += 1
                    ridx = np.asarray(d.recompute_idx, np.int64)
                    if ridx.size and not pool.write_rows(
                            table, base + ridx,
                            res.k_layers[:, seg.start + ridx],
                            res.v_layers[:, seg.start + ridx],
                            res.pos_layout[seg.start + ridx],
                            reservation=req.reservation):
                        return False
                    continue
            # miss (or pin failed, e.g. variant evicted mid-batch):
            # private block-aligned copy of this segment's final KV
            if pool.append_segment(
                    table, res.k_layers[:, seg.start:seg.end],
                    res.v_layers[:, seg.start:seg.end],
                    res.pos_layout[seg.start:seg.end],
                    reservation=req.reservation) is None:
                return False
        q = plan.question
        if q.length == 0:
            return True
        return pool.append_segment(
            table, res.k_layers[:, q.start:q.end],
            res.v_layers[:, q.start:q.end], res.pos_layout[q.start:q.end],
            reservation=req.reservation) is not None

    def _release_runs(self, req: Request):
        for run in req.shared_runs:
            self.store.release_pool_run(run)
        req.shared_runs = []

    def _estimate_blocks(self, req: Request) -> int:
        """Delta-aware admission estimate: segments covered by an
        already-resident shared run cost zero new blocks; everything
        else is counted at block-aligned granularity (plus the question
        + decode tail). CoW clones beyond the estimate fall back to the
        free list. Strategies whose hit logic diverges from
        ``best_variant`` (prefix) reserve the full estimate, as does a
        retry after a failed zero-copy write-back (``reserve_full``) —
        the pairing with the copy-style write-back guarantees the
        retry cannot fail again for lack of blocks.

        Layout and hit selection must mirror ``build_plan`` (same
        ``prompt_hashes``, same cumulative starts, same ``best_variant``
        probe) — a mismatched residency key would under-reserve and
        push write-backs onto the defensive burn-requeue path."""
        bs = self.pool.block_size
        if req.reserve_full:
            # the escalated retry writes back copy-style (dense
            # write_prefill), whose need is the DENSE block count —
            # the per-segment aligned sum below would overshoot it and
            # could trip the scheduler's can-never-fit fail-fast on
            # pools the copy path serves
            req.delta_blocks_saved = 0
            return self.pool.blocks_needed(Scheduler._need(req))
        parts = [np.asarray(req.system_tokens)] + \
            [np.asarray(c) for c in req.chunk_tokens]
        if req.prompt_hashes is None:
            req.prompt_hashes = prompt_hashes(parts[0], parts[1:])
        hashes = req.prompt_hashes
        residency = self.store.residency
        # a strategy whose hit logic diverges from the best_variant
        # probe declares predicts_residency=False in the registry
        predict = self.executor.strategy_obj.predicts_residency
        blocks = full = 0
        start = 0
        for i, part in enumerate(parts):
            n = -(-len(part) // bs)
            full += n
            shared = False
            if predict and residency is not None:
                hit = self.store.best_variant(hashes[i], hashes[:i])
                shared = hit is not None and \
                    residency.resident(hit[0].variant_id, start)
            if not shared:
                blocks += n
            start += len(part)
        tail = -(-(len(req.question_tokens) + req.max_new_tokens) // bs)
        req.delta_blocks_saved = full - blocks
        return blocks + tail

    def _teardown(self, req: Request) -> int:
        """Release every pool resource a request's burned attempt
        holds: shared-run reader refs, table blocks, and the open
        reservation (one compound ``KVPool.reclaim_request``). Shared
        by the requeue, preemption, and deadline-expiry paths. Returns
        the blocks returned to the free list — deferred unpins that the
        last reader's release triggered included, which is why the
        count is measured around the whole teardown rather than taken
        from ``reclaim_request`` alone."""
        if req.prefetch_ticket is not None:
            # retract tier promotions still queued for this request —
            # a torn-down attempt must not keep flushing the HBM tier
            req.prefetch_ticket.cancel()
            req.prefetch_ticket = None
            self.counters.prefetch_cancels += 1
        before = self.pool.free_blocks
        self._release_runs(req)
        self.pool.reclaim_request(req.table, req.reservation)
        req.reservation = None
        return self.pool.free_blocks - before

    def _requeue(self, req: Request):
        """Return a request to the queue with its per-attempt state
        reset: KV table freed, reservation cancelled, and every
        attempt-scoped field cleared (``Request.reset_attempt`` — a
        retry re-prefills from scratch, so stale ``output_tokens``
        would corrupt the output and stale ``t_first_token`` /
        ``prefill_tokens_*`` / ``cache_hits`` would report metrics
        from the discarded pass)."""
        self._teardown(req)
        req.reset_attempt()
        self.scheduler.requeue(req)

    def _preempt(self, req: Request):
        """Preempt one decode request for a starved queue head: leave
        its decode row, tear down its pool state (the recovered blocks
        are what the head's retried admission reserves from), and reset
        it for re-entry as a normal prefill — ``reserve_full`` cleared,
        so it shares any still-resident runs it just released instead
        of escalating to a full copy-style reservation. The caller
        (``step``) requeues it at the queue front *after* retrying
        admission for the head."""
        row = next((i for i, r in enumerate(self._rows) if r is req),
                   None)
        self.decoding.remove(req)
        if row is not None:
            self._decode_leave(row)
        else:
            # admitted while a rebuild was pending: never entered the
            # row map, so membership just changed under the stale cache
            self._needs_rebuild = True
        recovered = self._teardown(req)
        req.reserve_full = False
        req.reset_attempt()
        self.counters.preemptions += 1
        self.counters.preempt_block_recovered += recovered

    # ---- decode batch -------------------------------------------------------
    def _row_capacity(self, req: Request) -> int:
        """Arena sequence slots this request may touch while decoding
        (the arena holds the compact logical view, so capacity follows
        ``total_len``, not the block-aligned table length)."""
        return req.total_len + req.max_new_tokens + 1

    def _rebuild_decode_batch(self):
        B = _bucket(len(self.decoding), self.decode_bucket_b)
        max_len = max(self._row_capacity(r) for r in self.decoding)
        S = _bucket(max_len, self.seq_bucket)
        if self.paged_decode:
            # paged rebuild = re-bucket the index tensor: the slot rows
            # are re-exported from the block tables every step anyway
            # (they are [B, S] int32, not KV), so a membership change
            # that grows (B, S) costs a row-map reset and nothing else —
            # no gather, no transfer (decode_gather_bytes unchanged)
            self._dshape = (B, S)
            self._rows = list(self.decoding) + \
                [None] * (B - len(self.decoding))
            self._masked_rows = set()
            self._needs_rebuild = False
            self.counters.decode_rebuilds += 1
            return
        L = self.cfg.num_layers
        hkv, dh = self.cfg.num_kv_heads, self.cfg.head_dim_
        k = np.zeros((L, B, S, hkv, dh), np.float32)
        v = np.zeros_like(k)
        pos = np.full((B, S), -1, np.int32)
        for i, r in enumerate(self.decoding):
            kk, vv, pp = self.pool.gather(r.table, S, compact=True)
            self.counters.decode_gather_bytes += kk.nbytes + vv.nbytes
            k[:, i], v[:, i], pos[i] = kk, vv, pp
        # to model cache format (batched pack)
        P, G = len(self.cfg.pattern), self.cfg.n_groups
        groups = []
        if G:
            kg = k[:G * P].reshape(G, P, B, S, hkv, dh)
            vg = v[:G * P].reshape(G, P, B, S, hkv, dh)
            for p in range(P):
                groups.append({"k": jnp.asarray(kg[:, p]),
                               "v": jnp.asarray(vg[:, p]),
                               "pos": jnp.broadcast_to(
                                   jnp.asarray(pos), (G, B, S))})
        tail = [{"k": jnp.asarray(k[G * P + i]),
                 "v": jnp.asarray(v[G * P + i]),
                 "pos": jnp.asarray(pos)} for i in range(self.cfg.n_tail)]
        self._dcache = {"groups": groups, "tail": tail}
        self._dshape = (B, S)
        self._rows = list(self.decoding) + [None] * (B - len(self.decoding))
        self._masked_rows = set()
        self._needs_rebuild = False
        self.counters.decode_rebuilds += 1

    def _decode_join_batch(self, reqs: Sequence[Request]):
        """Join newly-decoding requests into the decode batch in place,
        or fall back to a full rebuild (flag only — the rebuild itself
        is lazy) when there is no cache yet, not enough free rows, or
        the row arena is too short for any of them. The all-or-nothing
        check runs before the first join so a rebuild-forcing member
        does not waste the earlier members' gathers and transfers."""
        if not reqs:
            return
        have_batch = self._dshape is not None if self.paged_decode \
            else self._dcache is not None
        if not self.incremental_decode or not have_batch or \
                self._needs_rebuild:
            self._needs_rebuild = True
            return
        _B, S = self._dshape
        if len(reqs) > self._rows.count(None) or \
                any(self._row_capacity(r) > S for r in reqs):
            self._needs_rebuild = True
            return
        for req in reqs:
            self._decode_join(req)

    def _decode_join(self, req: Request):
        """Write one newly-decoding request's gathered KV into a free
        batch row in place (capacity pre-checked by
        ``_decode_join_batch``)."""
        _B, S = self._dshape
        row = self._rows.index(None)
        if not self.paged_decode:
            # arena join: the only path that copies KV to admit a
            # request into the decode batch. Paged joins stop here —
            # the request's slot rows are exported (int32 indices, not
            # KV) at the next step
            k, v, pos = self.pool.gather(req.table, S, compact=True)
            self.counters.decode_gather_bytes += k.nbytes + v.nbytes
            self.counters.decode_join_copies += 1
            self._dcache = _join_row_fn(self.cfg)(
                self._dcache, jnp.int32(row), jnp.asarray(k),
                jnp.asarray(v), jnp.asarray(pos))
        self._rows[row] = req
        self.counters.decode_joins += 1
        if row in self._masked_rows:
            self._masked_rows.discard(row)
            self.counters.decode_rows_recycled += 1

    def _decode_leave(self, row: int):
        """Mask a departing request's batch row: position row -> -1 kills
        every key in the row's attention; the row is recycled by the
        next join. In rebuild mode the whole batch is regathered
        instead."""
        self._rows[row] = None
        if not self.incremental_decode:
            self._needs_rebuild = True
            return
        if self.paged_decode:
            # paged leave: pure row-map update — the departed table's
            # slots simply stop being referenced by any index row
            if self._dshape is None or self._needs_rebuild:
                return
            self._masked_rows.add(row)
            self.counters.decode_leaves += 1
            return
        if self._dcache is None or self._needs_rebuild:
            return
        self._dcache = _leave_row_fn(self.cfg)(self._dcache,
                                               jnp.int32(row))
        self._masked_rows.add(row)
        self.counters.decode_leaves += 1

    def _sync_dirty_blocks(self):
        """Upload the pool's dirty-block log into the device twin: one
        jitted scatter of the touched blocks' flat slots (the id list
        is bucketed so churny step counts do not retrace). Host writes
        that dirty blocks — prefill write-back, CoW clones, recompute
        fixup rows, freshly-opened append blocks — are exactly the
        block-granular transfers a paged deployment pays, so they are
        counted honestly (``paged_block_syncs`` / ``paged_sync_bytes``)
        instead of hidden inside a wholesale re-pack."""
        ids = self.pool.dirty_blocks()
        if not ids:
            return
        kp, vp, pp = self.pool.block_view()
        bs = self.pool.block_size
        m = _bucket(len(ids), 8)
        bid = np.full(m, -1, np.int64)
        bid[:len(ids)] = ids
        slots = bid[:, None] * bs + np.arange(bs)[None, :]
        slots = np.where(bid[:, None] >= 0, slots, -1).reshape(-1)
        idx = np.maximum(bid, 0)
        k = kp[:, idx].reshape(kp.shape[0], m * bs, *kp.shape[3:])
        v = vp[:, idx].reshape(vp.shape[0], m * bs, *vp.shape[3:])
        pos = np.where(slots >= 0, pp[idx].reshape(m * bs), -1)
        self._pcache = self._psync(
            self._pcache, jnp.asarray(slots, jnp.int32), jnp.asarray(k),
            jnp.asarray(v), jnp.asarray(pos, jnp.int32))
        self.counters.paged_block_syncs += len(ids)
        self.counters.paged_sync_bytes += int(
            kp[:, ids].nbytes + vp[:, ids].nbytes)
        self.pool.clear_dirty(ids)

    def _extract_pool_slot_kv(self, slot: int):
        """Read one flat pool slot's per-layer KV back from the device
        twin (the jitted pass scattered the new token there). This is
        the host mirror's source, so host pool bytes and twin bytes
        agree bit-for-bit by construction — which is what lets
        ``append_token`` below clear the block's dirty mark instead of
        re-uploading it next step."""
        cfg = self.cfg
        P, G = len(cfg.pattern), cfg.n_groups
        hkv, dh = cfg.num_kv_heads, cfg.head_dim_
        k = np.zeros((cfg.num_layers, hkv, dh), np.float32)
        v = np.zeros((cfg.num_layers, hkv, dh), np.float32)
        for p in range(P):
            kk = np.asarray(self._pcache["groups"][p]["kp"][:, slot])
            vv = np.asarray(self._pcache["groups"][p]["vp"][:, slot])
            for g in range(G):
                k[g * P + p] = kk[g]
                v[g * P + p] = vv[g]
        for i in range(cfg.n_tail):
            k[G * P + i] = np.asarray(self._pcache["tail"][i]["kp"][slot])
            v[G * P + i] = np.asarray(self._pcache["tail"][i]["vp"][slot])
        return k, v

    def _run_decode_step_paged(self):
        """One decode iteration, block-table-native: attention reads
        K/V in place from the pool twin through per-request compact
        slot-index rows (``KVPool.table_slot_index``) — no per-request
        gather is formed, joins/leaves were row-map updates, and the
        rebuild only re-bucketed (B, S).

        Per-step ordering: (1) pre-open every live row's append slot
        (``ensure_append_slot`` — the one step that can fail under pool
        pressure, so the failure escalation the arena path applies
        *after* the pass happens here *before* any compute is spent);
        (2) bring the device twin up to date (initial wholesale pack,
        then dirty-block scatters); (3) run the jitted pass, which
        splices each row's pre-opened slot into its index row and
        scatters the new token's KV there; (4) mirror that KV into the
        host pool (``append_token`` cannot fail — the slot is open) and
        drop the block from the dirty log, since host and device now
        hold identical bytes."""
        if self._dshape is None or self._needs_rebuild:
            self._rebuild_decode_batch()
        B, S = self._dshape
        pslots = np.full(B, -1, np.int32)
        for i, r in enumerate(list(self._rows)):
            if r is None:
                continue
            s = self.pool.ensure_append_slot(r.table,
                                             reservation=r.reservation)
            if s is None:
                # zero-copy: CoW fixups may have drained the delta
                # reservation — escalate to a full reservation, same
                # as the arena path's post-step append failure
                r.reserve_full = True
                self.decoding.remove(r)
                self._decode_leave(i)
                self._requeue(r)
                continue
            pslots[i] = s
        if not self.decoding:
            return
        if self._pcache is None:
            from repro.core.prefill import pack_paged_cache
            self._pcache = pack_paged_cache(self.cfg,
                                            *self.pool.block_view())
            self.pool.clear_dirty(self.pool.dirty_blocks())
        else:
            self._sync_dirty_blocks()
        toks = np.zeros(B, np.int32)
        poss = np.full(B, -1, np.int32)
        rows = np.full((B, S), -1, np.int32)
        for i, r in enumerate(self._rows):
            if r is None:
                continue
            toks[i] = r.output_tokens[-1]
            poss[i] = r.total_len       # logical position (RoPE/causal)
            rows[i] = self.pool.table_slot_index(r.table, S)
        brows = None
        if self._paged_kernel:
            # the Pallas kernel iterates physical blocks, so it needs
            # the block-id rows too (bucketed to bound retraces); all
            # held blocks count — the pre-opened append block's unused
            # slots carry pos == -1 and mask out in-kernel
            nbm = _bucket(max(len(r.table.blocks) for r in self._rows
                              if r is not None), 8)
            brows = np.full((B, nbm), -1, np.int32)
            for i, r in enumerate(self._rows):
                if r is not None:
                    brows[i] = self.pool.table_block_row(r.table, nbm)
        t0 = time.perf_counter()
        logits, self._pcache = self._paged_fn(
            self.params, jnp.asarray(toks), jnp.asarray(poss),
            self._pcache, jnp.asarray(pslots), jnp.asarray(rows),
            None if brows is None else jnp.asarray(brows))
        logits = np.asarray(logits[:, 0])
        self.clock += (time.perf_counter() - t0) * self.time_scale
        self.stats.decode_steps += 1
        self._count_attn_flops(B, S)
        if self.trace_decode:
            self.decode_trace.append(
                {r.rid: logits[i].copy()
                 for i, r in enumerate(self._rows) if r is not None})
        for i, r in enumerate(list(self._rows)):
            if r is None:
                continue
            nxt = int(np.argmax(logits[i, :self.cfg.vocab_size]))
            ktok, vtok = self._extract_pool_slot_kv(int(pslots[i]))
            self.pool.append_token(r.table, ktok, vtok, r.total_len,
                                   reservation=r.reservation)
            self.pool.clear_dirty([int(pslots[i])
                                   // self.pool.block_size])
            r.output_tokens.append(nxt)
            self._emit_token(r, nxt)
            r.total_len += 1
            if len(r.output_tokens) >= r.max_new_tokens:
                r.state = State.DONE
                r.t_done = self.clock
                self.stats.completed += 1
                self.decoding.remove(r)
                self._decode_leave(i)
                if self.trace_decode:
                    pad = _bucket(max(r.table.length, 1), self.seq_bucket)
                    self.final_kv[r.rid] = self.pool.gather(r.table, pad)
                self.pool.free_table(r.table)
                self._release_runs(r)
                self.pool.commit(r.reservation)
                r.reservation = None
                self.scheduler.on_terminal(r)

    def _run_decode_step(self):
        if self.paged_decode:
            return self._run_decode_step_paged()
        if self._dcache is None or self._needs_rebuild:
            self._rebuild_decode_batch()
        B, S = self._dshape
        toks = np.zeros(B, np.int32)
        poss = np.full(B, -1, np.int32)
        slots = np.full(B, -1, np.int32)
        for i, r in enumerate(self._rows):
            if r is None:                  # masked row: inert (see
                continue                   # decode_fn row-masking)
            toks[i] = r.output_tokens[-1]
            poss[i] = r.total_len          # logical position (RoPE/causal)
            slots[i] = r.total_len         # arena append slot (compact
            #   logical view; the pool's block-aligned slot is private
            #   to append_token below)
        t0 = time.perf_counter()
        logits, self._dcache = self._decode_fn(
            self.params, jnp.asarray(toks), jnp.asarray(poss), self._dcache,
            jnp.asarray(slots))
        logits = np.asarray(logits[:, 0])
        self.clock += (time.perf_counter() - t0) * self.time_scale
        self.stats.decode_steps += 1
        self._count_attn_flops(B, S)
        if self.trace_decode:
            self.decode_trace.append(
                {r.rid: logits[i].copy() for i, r in enumerate(self._rows)
                 if r is not None})

        for i, r in enumerate(list(self._rows)):
            if r is None:
                continue
            nxt = int(np.argmax(logits[i, :self.cfg.vocab_size]))
            # persist the newly written KV into the paged pool
            ktok, vtok = self._extract_slot_kv(i, r.total_len)
            if not self.pool.append_token(r.table, ktok, vtok,
                                          r.total_len,
                                          reservation=r.reservation):
                # zero-copy: CoW fixups may have drained the delta
                # reservation write_rows drew on — escalate the retry
                # to a full reservation like the write-back burn path,
                # so the request cannot exhaust retries and FAIL where
                # the copy path would have served it
                r.reserve_full = True
                self.decoding.remove(r)
                self._decode_leave(i)
                self._requeue(r)
                continue
            r.output_tokens.append(nxt)
            self._emit_token(r, nxt)
            r.total_len += 1
            if len(r.output_tokens) >= r.max_new_tokens:
                r.state = State.DONE
                r.t_done = self.clock
                self.stats.completed += 1
                self.decoding.remove(r)
                self._decode_leave(i)
                if self.trace_decode:
                    pad = _bucket(max(r.table.length, 1), self.seq_bucket)
                    self.final_kv[r.rid] = self.pool.gather(r.table, pad)
                self.pool.free_table(r.table)
                self._release_runs(r)
                self.pool.commit(r.reservation)
                r.reservation = None
                self.scheduler.on_terminal(r)

    def _extract_slot_kv(self, batch_idx: int, slot: int):
        cfg = self.cfg
        P, G = len(cfg.pattern), cfg.n_groups
        L = cfg.num_layers
        hkv, dh = cfg.num_kv_heads, cfg.head_dim_
        k = np.zeros((L, hkv, dh), np.float32)
        v = np.zeros((L, hkv, dh), np.float32)
        for p in range(P):
            kk = np.asarray(self._dcache["groups"][p]["k"]
                            [:, batch_idx, slot])
            vv = np.asarray(self._dcache["groups"][p]["v"]
                            [:, batch_idx, slot])
            for g in range(G):
                k[g * P + p] = kk[g]
                v[g * P + p] = vv[g]
        for i in range(cfg.n_tail):
            k[G * P + i] = np.asarray(
                self._dcache["tail"][i]["k"][batch_idx, slot])
            v[G * P + i] = np.asarray(
                self._dcache["tail"][i]["v"][batch_idx, slot])
        return k, v

    # ---- workload driver ------------------------------------------------------
    def step_until_idle(self, *, max_iters: Optional[int] = None,
                        feed=None, on_step=None, idle=None) -> int:
        """The one serving loop ``run`` (batch replay) and the online
        server share — step until there is no work left, with the
        idle/clock-advance policy factored out of both callers:

        * ``feed() -> Optional[float]`` — submit every request whose
          arrival is due and return the next *future* arrival time
          (None when no more arrivals are known). Batch replay feeds
          from a sorted trace; the server feeds from its live inbox.
        * ``on_step()`` — called after every ``step`` (the server
          drains token events here, inside the engine thread).
        * ``idle() -> bool`` — a step did no work and nothing is
          queued or known to arrive. Return True to keep looping (the
          server blocks briefly on its inbox); None/False stops (batch
          replay is done).

        When a step does no work but arrivals are still pending, the
        clock jumps to the next arrival; when the queue is non-empty
        the loop keeps stepping (waiting on reserve headroom). Returns
        the number of iterations executed.

        ``max_iters=None`` (the default) is unbounded — what a
        long-lived serving loop needs, where any finite bound would
        eventually kill the engine thread mid-flight. Batch replay
        (``run``) passes an explicit bound as a runaway backstop."""
        iters = 0
        while max_iters is None or iters < max_iters:
            nxt = feed() if feed is not None else None
            if not (self.scheduler.queue or self.decoding
                    or nxt is not None):
                if idle is not None and idle():
                    continue
                break
            iters += 1
            worked = self.step()
            if on_step is not None:
                on_step()
            if not worked:
                if nxt is not None:      # idle: jump to next arrival
                    self.clock = max(self.clock, nxt)
                elif self.scheduler.queue:
                    continue             # waiting on reserve headroom
                elif not (idle is not None and idle()):
                    break
        return iters

    def run(self, requests: Sequence[Request],
            max_iters: int = 1_000_000) -> EngineStats:
        pending = sorted(requests, key=lambda r: r.arrival_time)
        i = 0

        def feed():
            nonlocal i
            while i < len(pending) and \
                    pending[i].arrival_time <= self.clock:
                self.submit(pending[i])
                i += 1
            return pending[i].arrival_time if i < len(pending) else None

        self.step_until_idle(max_iters=max_iters, feed=feed)
        self.stats.clock = self.clock
        self.stats.failed = sum(1 for r in requests
                                if r.state == State.FAILED)
        if self.store is not None and self.store.tiers is not None:
            tstats = self.store.tiers.stats
            self.stats.tier_quant_bytes_saved = \
                int(tstats.get("quant_bytes_saved", 0))
            self.stats.tier_dequant_loads = \
                int(tstats.get("dequant_loads", 0))
        return self.stats

    def stats_dict(self) -> dict:
        """One merged stats payload (the ``/stats`` endpoint body, also
        what benches record): engine stats + counters + pool occupancy.
        """
        d = self.stats.stats_dict()
        d["counters"] = self.counters.stats_dict()
        d["pool"] = dict(num_blocks=self.pool.num_blocks,
                         free_blocks=self.pool.free_blocks,
                         live_blocks=self.pool.live_blocks,
                         reserved_blocks=self.pool.reserved_blocks)
        return d
