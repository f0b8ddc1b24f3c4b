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

Decode batch (paged, row-map scheme): each decode step reads K/V in
place from a device twin of the pool's block arenas, through per-request
compact slot-index rows (``KVPool.table_slot_index``). The batch is a
bucketed (B, S) index tensor with a request-per-row map: a join takes a
free row, a leave empties it (an empty row passes position and slot -1,
so it is inert) and the next join recycles it. No KV is copied for
either. A rebuild only re-buckets (B, S) and resets the row map, when
the shape must grow.

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
import threading
import time
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chunkstore import ChunkStore, prompt_hashes
from repro.core.prefill import CacheCraftExecutor, inject_chunk_kv, \
    pack_paged_cache, paged_decode_fn, paged_sync_fn, paged_twin_write_fn, \
    prefill_len, zero_prefill_kv
from repro.core.preload import preload_depth
from repro.core.tiers import PrefetchTicket
from repro.models.config import ModelConfig
from repro.serving.kvpool import KVPool
from repro.serving.metrics import ServingCounters
from repro.serving.request import Request, State
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.tracing import span, to_host


# attn_impl -> (prefill-window backend, paged decode backend). Any other
# name, and None, leaves the prefill to the executor (None: its default)
# and decodes through the ``paged`` gather route. Under a serving mesh
# the prefill windows run ``sharded``; the ``paged`` gather then hands
# its operands to ``sharded`` too, and ``paged_kernel`` runs per shard.
_ROUTES = {
    "kernel": ("kernel", "paged_kernel"),
    "paged_kernel": ("kernel", "paged_kernel"),
    "paged": ("dense", "paged"),
}


def _bucket(n: int, b: int) -> int:
    return max(b, -(-n // b) * b)


def _sync_bucket(n: int) -> int:
    """Blocks in one dirty-block upload: powers of two below 8, then
    multiples of 8. The padding is uploaded too, so a lone
    decode-opened block moves one block, not eight."""
    return 1 << (n - 1).bit_length() if n < 8 else _bucket(n, 8)


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
                 share_chunk_kv: bool = True,
                 trace_decode: bool = False,
                 attn_impl: Optional[str] = None,
                 mesh=None):
        self.cfg = cfg
        self.store = store
        # attention backends (models.backend.BACKENDS), from ``_ROUTES``.
        # A serving mesh brings a matching head-sharded pool layout; the
        # mesh must be installed before the first trace of any jit root
        # that runs under it. Params are replicated onto the mesh, so
        # every shard reads its own copy instead of pulling from
        # wherever init left them.
        prefill_impl, decode_impl = _ROUTES.get(attn_impl,
                                                (attn_impl, "paged"))
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
            prefill_impl = "sharded"
        self.params = params
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
        if prefill_impl is not None:
            ek.setdefault("attn_impl", prefill_impl)
        self.counters = ServingCounters()
        self.executor = CacheCraftExecutor(cfg, params, store,
                                           counters=self.counters, **ek)
        self.scheduler = Scheduler(sched or SchedulerConfig())
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
        self.clock = 0.0
        self.decoding: List[Request] = []
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
        # paged decode (block-table-native attention): the decode pass
        # reads K/V in place from a device twin of the pool's block
        # arenas, indexed per request by compact slot rows — joins and
        # leaves are row-map updates, rebuilds only re-bucket the
        # index tensor, and the new token's KV is scattered into its
        # pre-opened pool slot inside the jitted pass. Blocks a prefill
        # fills from its own result (private segments, copy-mode
        # writes) reach the twin on the device, gathered from the
        # pass's device KV (twin_write_blocks / twin_write_bytes). Every
        # other host write — shared-run loads, CoW clones, recompute
        # fixup rows, decode-opened append blocks, prefills before the
        # twin's first pack — stays in the pool's dirty-block log and is
        # uploaded before the next step (paged_block_syncs /
        # paged_sync_bytes).
        self._pcache = None
        self._paged_kernel = decode_impl == "paged_kernel"
        self._paged_fn = paged_decode_fn(cfg, decode_impl, block_size)
        self._psync = paged_sync_fn(cfg)
        self._twin_write = paged_twin_write_fn(cfg)

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
                    # changed under the stale row map (same edge as
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
        with span("cc.step"):
            with span("cc.admit"):
                reqs, worked = self._admission()
            if reqs:
                with span("cc.prefill", n=len(reqs),
                          rids=" ".join(str(r.rid) for r in reqs)):
                    self._run_prefills(reqs)
                worked = True
            if self.decoding:
                with span("cc.decode", b=len(self.decoding)):
                    self._run_decode_step()
                worked = True
            return worked

    def _admission(self) -> Tuple[List[Request], bool]:
        """The top of an iteration: cancels, queue expiry, look-ahead
        prefetch and admission, with reclaim, preemption or the
        shortage valve when the queue head cannot reserve. Returns the
        admitted requests and whether any work was done."""
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
        return reqs, worked

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

        with span("cc.prefill.write_back"):
            joined: List[Request] = []
            twin_runs: List[tuple] = []
            for req, res in zip(reqs, results):
                runs: List[tuple] = []
                ok = self._write_back(req, res, runs)
                if ok:
                    twin_runs += runs
                else:
                    # copy path: unreachable with reserve-at-admission
                    # (counted so tests can assert 0). Zero-copy path:
                    # the delta estimate does not budget CoW clones, so
                    # a tight pool can fail the write-back — escalate
                    # the retry to a full reservation + copy-style
                    # write-back, which the reservation then covers by
                    # construction.
                    self.counters.burn_requeues += 1
                    req.reserve_full = True
                    self._requeue(req)
                    continue
                first = int(np.argmax(
                    res.logits_last[:self.cfg.vocab_size]))
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
                self.stats.prefill_tokens_computed += \
                    res.plan.num_active_tokens
                self.counters.delta_blocks_saved += req.delta_blocks_saved
                req.delta_blocks_saved = 0
                self.decoding.append(req)
                joined.append(req)
            self._decode_join_batch(joined)
        if twin_runs and self._pcache is not None:
            with span("cc.prefill.twin"):
                self._write_twin(results[0].device_kv, twin_runs)
        for res in results:
            res.device_kv = None

    def _write_twin(self, kv, runs: Sequence[tuple]):
        """Fill freshly written pool blocks in the device twin from the
        prefill pass's own device KV ``kv``: one jitted gather from the
        packed layout and scatter into the twin, then the blocks leave
        the dirty log, so ``_sync_dirty_blocks`` does not upload them.
        ``runs`` are ``(blocks, layout_row, n, pos)``: ``n`` tokens from
        packed row ``layout_row`` on, block-aligned into ``blocks``, the
        tail padding zero K/V at position -1 exactly as ``write_run``
        leaves the host pool."""
        bs = self.pool.block_size
        m = self._twin_map_len(prefill_len(kv), len(runs))
        slots = np.full(m, -1, np.int32)
        src = np.full(m, -1, np.int32)
        pos = np.full(m, -1, np.int32)
        i = 0
        for blocks, row, n, p in runs:
            ids = np.asarray(blocks, np.int64)
            slots[i:i + ids.size * bs] = \
                (ids[:, None] * bs + np.arange(bs)).reshape(-1)
            src[i:i + n] = np.arange(row, row + n)
            pos[i:i + n] = p
            i += ids.size * bs
        self._pcache = self._twin_write(
            self._pcache, kv, jnp.asarray(slots), jnp.asarray(src),
            jnp.asarray(pos))
        ids = [b for blocks, *_ in runs for b in blocks]
        self.pool.clear_dirty(ids)
        self.counters.twin_write_blocks += len(ids)
        self.counters.twin_write_bytes += len(ids) * self.pool.block_nbytes

    def _twin_map_len(self, S: int, n_runs: int) -> int:
        """Slots in a twin-write map: ``ceil(S / block)`` blocks plus the
        run count bucketed to 8 (each run pads at most one block), so
        any runs of a pass fit and the shape follows the prefill bucket
        ``S`` alone while a pass has at most 8 runs."""
        bs = self.pool.block_size
        return (-(-S // bs) + _bucket(n_runs, 8)) * bs

    def _warm_twin_programs(self):
        """Compile, with all -1 maps that write nothing, the twin write
        for every single-request prefill bucket up to ``seq_bucket`` and
        the dirty-block upload of 1, 2, 4 and 8 blocks, so the first
        real prefill of each length, and the first upload of a few
        decode-opened blocks, run programs that are already built."""
        b = self.executor.bucket
        for S in range(b, max(b, self.seq_bucket) + 1, b):
            none = jnp.full(self._twin_map_len(S, 1), -1, jnp.int32)
            self._pcache = self._twin_write(
                self._pcache, zero_prefill_kv(self.cfg, S), none, none, none)
        k = self.pool.k
        for m in sorted({_sync_bucket(n) for n in range(1, 9)}):
            z = jnp.zeros((k.shape[0], m * k.shape[2]) + k.shape[3:], k.dtype)
            none = jnp.full(m * k.shape[2], -1, jnp.int32)
            self._pcache = self._psync(self._pcache, none, z, z, none)

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
            pos = np.arange(start, start + length, dtype=np.int32)
            k, v = inject_chunk_kv(self.cfg, kv, pos, self.counters)
            return k, v, pos
        return load

    def _write_back(self, req: Request, res, runs: List[tuple]) -> bool:
        """Persist one prefill result into the request's block table.

        Copy mode: one dense ``write_prefill``. Zero-copy mode: segment
        by segment — hit chunks attach the store's canonical shared run
        (recompute-fixup rows CoW into this table), everything else
        (miss chunks, the question) gets fresh block-aligned segments.
        Non-recompute rows of a hit segment are never touched by the
        windowed pass, so shared-run bytes + per-request fixups
        reproduce the copy path's KV exactly. Every run of fresh blocks
        filled straight from the result is appended to ``runs``, as
        ``_write_twin`` takes them."""
        pool, plan = self.pool, res.plan
        if not self.share_chunk_kv or req.reserve_full:
            if not pool.write_prefill(req.table, res.k_layers,
                                      res.v_layers, res.pos_layout,
                                      reservation=req.reservation):
                return False
            n = res.k_layers.shape[1]
            runs.append((req.table.blocks[:pool.blocks_needed(n)],
                         res.layout_off, n, res.pos_layout))
            return True
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
            if not self._append_private(req, res, seg, runs):
                return False
        q = plan.question
        return q.length == 0 or self._append_private(req, res, q, runs)

    def _append_private(self, req: Request, res, seg, runs) -> bool:
        """Fresh block-aligned segment ``seg`` of the result, appended
        to the request's table and noted in ``runs``."""
        table, n0 = req.table, len(req.table.blocks)
        if self.pool.append_segment(
                table, res.k_layers[:, seg.start:seg.end],
                res.v_layers[:, seg.start:seg.end],
                res.pos_layout[seg.start:seg.end],
                reservation=req.reservation) is None:
            return False
        runs.append((table.blocks[n0:], res.layout_off + seg.start,
                     seg.length, res.pos_layout[seg.start:seg.end]))
        return True

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
            # row map, so membership just changed under the stale row map
            self._needs_rebuild = True
        recovered = self._teardown(req)
        req.reserve_full = False
        req.reset_attempt()
        self.counters.preemptions += 1
        self.counters.preempt_block_recovered += recovered

    # ---- decode batch -------------------------------------------------------
    def _row_capacity(self, req: Request) -> int:
        """Columns of the (B, S) slot-index rows this request may fill
        while decoding: one per logical token (the rows are the compact
        view, so capacity follows ``total_len``, not the block-aligned
        table length)."""
        return req.total_len + req.max_new_tokens + 1

    def _rebuild_decode_batch(self):
        """Re-bucket (B, S) and reset the row map: the slot rows are
        exported from the block tables every step (int32 indices, not
        KV), so a membership change that grows the shape costs nothing
        else."""
        B = _bucket(len(self.decoding), self.decode_bucket_b)
        max_len = max(self._row_capacity(r) for r in self.decoding)
        self._dshape = (B, _bucket(max_len, self.seq_bucket))
        self._rows = list(self.decoding) + [None] * (B - len(self.decoding))
        self._masked_rows = set()
        self._needs_rebuild = False
        self.counters.decode_rebuilds += 1

    def _decode_join_batch(self, reqs: Sequence[Request]):
        """Join newly-decoding requests into free rows of the decode
        batch, or fall back to a rebuild (flag only — the rebuild itself
        is lazy) when there is no batch yet, not enough free rows, or
        the rows are too short for any of them."""
        if not reqs:
            return
        if self._dshape is None or self._needs_rebuild:
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
        """Put one newly-decoding request in a free batch row (capacity
        pre-checked by ``_decode_join_batch``); its slot rows are
        exported at the next step."""
        row = self._rows.index(None)
        self._rows[row] = req
        self.counters.decode_joins += 1
        if row in self._masked_rows:
            self._masked_rows.discard(row)
            self.counters.decode_rows_recycled += 1

    def _decode_leave(self, row: int):
        """Empty a departing request's batch row: the departed table's
        slots stop being referenced by any index row, and the row is
        recycled by the next join."""
        self._rows[row] = None
        if self._dshape is None or self._needs_rebuild:
            return
        self._masked_rows.add(row)
        self.counters.decode_leaves += 1

    def _sync_dirty_blocks(self):
        """Upload the pool's dirty-block log into the device twin: one
        jitted scatter of the touched blocks' flat slots (the id list
        is bucketed, ``_sync_bucket``, so churny step counts do not
        retrace). The log holds
        the host writes whose bytes are not on the device already:
        shared-run loads, CoW clones, recompute fixup rows and
        freshly-opened append blocks — block-granular transfers a paged
        deployment pays, counted honestly (``paged_block_syncs`` /
        ``paged_sync_bytes``) instead of hidden inside a wholesale
        re-pack. A prefill's private segments left the log when
        ``_write_twin`` copied them on the device."""
        ids = self.pool.dirty_blocks()
        if not ids:
            return
        kp, vp, pp = self.pool.block_view()
        bs = self.pool.block_size
        m = _sync_bucket(len(ids))
        bid = np.full(m, -1, np.int64)
        bid[:len(ids)] = ids
        slots = bid[:, None] * bs + np.arange(bs)[None, :]
        slots = np.where(bid[:, None] >= 0, slots, -1).reshape(-1)
        idx = np.maximum(bid, 0)
        k = kp[:, idx].reshape(kp.shape[0], m * bs, *kp.shape[3:])
        v = vp[:, idx].reshape(vp.shape[0], m * bs, *vp.shape[3:])
        pos = np.where(slots >= 0, pp[idx].reshape(m * bs), -1)
        # int32 on the host: a device-side cast would be one more
        # program per bucket to compile
        self._pcache = self._psync(
            self._pcache, jnp.asarray(slots.astype(np.int32)),
            jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(pos.astype(np.int32)))
        self.counters.paged_block_syncs += len(ids)
        self.counters.paged_sync_bytes += int(
            kp[:, ids].nbytes + vp[:, ids].nbytes)
        self.pool.clear_dirty(ids)

    def _run_decode_step(self):
        """One decode iteration, block-table-native: attention reads
        K/V in place from the pool twin through per-request compact
        slot-index rows (``KVPool.table_slot_index``) — no per-request
        gather is formed, joins/leaves were row-map updates, and the
        rebuild only re-bucketed (B, S).

        Per-step ordering: (1) pre-open every live row's append slot
        (``ensure_append_slot`` — the one step that can fail under pool
        pressure, so a failure escalates *before* any compute is spent);
        (2) bring the device twin up to date (initial wholesale pack,
        then dirty-block scatters); (3) run the jitted pass, which
        splices each row's pre-opened slot into its index row and
        scatters the new token's KV there, and returns each row's argmax
        token and that KV — both fetched in one read; (4) mirror the KV
        into the host pool (``append_token`` cannot fail — the slot is
        open) and drop the block from the dirty log, since host and
        device now hold identical bytes."""
        with span("cc.decode.prepare"):
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
                    # reservation write_rows drew on — escalate the retry
                    # to a full reservation like the write-back burn path,
                    # so the request cannot exhaust retries and FAIL where
                    # the copy path would have served it
                    r.reserve_full = True
                    self.decoding.remove(r)
                    self._decode_leave(i)
                    self._requeue(r)
                    continue
                pslots[i] = s
            if not self.decoding:
                return
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
        with span("cc.decode.sync"):
            if self._pcache is None:
                self._pcache = pack_paged_cache(self.cfg,
                                                *self.pool.block_view())
                self.pool.clear_dirty(self.pool.dirty_blocks())
                self._warm_twin_programs()
            else:
                self._sync_dirty_blocks()
        with span("cc.decode.run"):
            t0 = time.perf_counter()
            (logits, nxt, new_kv), self._pcache = self._paged_fn(
                self.params, jnp.asarray(toks), jnp.asarray(poss),
                self._pcache, jnp.asarray(pslots), jnp.asarray(rows),
                None if brows is None else jnp.asarray(brows))
            nxt, new_kv = to_host((nxt, new_kv), self.counters)
            self.clock += (time.perf_counter() - t0) * self.time_scale
            self.stats.decode_steps += 1
            self._count_attn_flops(B, S)
            if self.trace_decode:
                logits = to_host(logits[:, 0], self.counters)
                self.decode_trace.append(
                    {r.rid: logits[i].copy()
                     for i, r in enumerate(self._rows) if r is not None})
        with span("cc.decode.mirror"):
            for i, r in enumerate(list(self._rows)):
                if r is None:
                    continue
                self.pool.append_token(r.table, new_kv[0, :, i],
                                       new_kv[1, :, i], r.total_len,
                                       reservation=r.reservation)
                self.pool.clear_dirty([int(pslots[i])
                                       // self.pool.block_size])
                tok = int(nxt[i])
                r.output_tokens.append(tok)
                self._emit_token(r, tok)
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
