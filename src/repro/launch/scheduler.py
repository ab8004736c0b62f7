"""SLO-aware continuous-batching scheduler over the unified entry point.

PR 1's serving path takes fixed, caller-assembled batches: whoever calls
``RAGServer.retrieve_batch`` decides the batch boundaries, so a trickle of
requests runs at B=1 and a burst waits for the whole burst to assemble.
This module adds the missing layer between callers and the engine:

  * :class:`MicroBatchScheduler` — an async request queue of typed
    :class:`~repro.core.Query` objects.  ``submit(Query(...))`` returns a
    future immediately; a flusher coroutine cuts micro-batches whenever
    ``max_batch`` requests are waiting **or** the earliest flush-by time
    passes (continuous batching: each flush takes whatever arrived, so
    batch sizes track the arrival process).
  * **SLO classes** (DESIGN.md §SLO-Aware Serving): the queue is per
    :class:`~repro.core.SLOClass`, and flush assembly is strict-priority —
    INTERACTIVE first, then STANDARD, then BULK riding along in whatever
    batch capacity is left.  BULK waits ``bulk_wait_factor`` × longer per
    flush (it exists to amortize, not to be prompt), and an INTERACTIVE
    request carrying ``deadline_ms`` tightens its own flush-by to half the
    deadline; when such a request is at risk the cut *preempts* the bulk
    backlog (flush reason ``"preempt"``): the batch takes only
    interactive/standard work so the deadline-sensitive answer is not
    queued behind a bulk scan.
  * **Admission control** (:class:`~repro.launch.admission
    .AdmissionController`): consulted at ``submit`` with the live per-class
    backlog and a queue-wait estimate (flush-time EMA × flushes ahead).  A
    shed request's future resolves immediately with a typed
    :class:`~repro.core.Rejected` — the scheduler never hangs or raises for
    back-pressure.
  * **Auth-aware answer cache** (:class:`~repro.core.AnswerCache`):
    consulted at ``submit`` after admission, keyed by (query key, role-mask
    words, k, efs); a hit resolves the future immediately with
    ``path="cache"`` and misses are populated when their flush retires.
    The store owner is responsible for invalidation (``DynamicStore`` does
    it precisely per mutation).
  * Each micro-batch runs through one ``store.search(queries)`` call — the
    batched lattice engine when every node engine supports it, per-query
    coordinated search otherwise.  ``min_packed_batch`` gates the packed
    leftover shard, and :class:`ServeStats` records which path each flush
    ran.
  * **Overlapping flushes** (``max_inflight``): with the default 1, flushes
    execute strictly one at a time.  On a multi-device
    :class:`~repro.core.sharded.ShardedVectorStore`, ``max_inflight > 1``
    lets flush N dispatch while flush N-1 is still executing.  The
    **device-aware cut policy** makes the overlap pay: while a flush is in
    flight, the cut prefers requests whose plan cover lands on device slots
    *disjoint* from the busy ones (``ShardedVectorStore.slots_for_roles``),
    deferring contenders to the next flush — so consecutive flushes occupy
    different device subsets instead of serializing on the same executor
    slots.  Requests past their flush-by time are never deferred.
  * :class:`ServeStats` — the versioned serving-stats contract
    (``summary()`` schema v2): totals, flush reasons, per-SLO-class
    sub-blocks (p50/p99, admitted/rejected/cancelled, cache hit rate),
    execution paths, per-device launches, and maintenance counters.

Mixing roles within a micro-batch remains free: the batched engine unions
plan covers, so co-scheduled roles share kernel launches on overlapping
lattice nodes, and the packed leftover shard amortizes disjoint tails.

Results are exactly the per-query coordinated-search answers for any flush
schedule (tests/test_scheduler.py, tests/test_slo_serving.py): the
engine's parity contract is schedule-independent, and the scheduler only
re-buckets rows.  SLO classes change *when* a query runs, never *what* it
answers.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .. import obs
from ..core import (DEFAULT_MIN_PACKED_BATCH, AnswerCache, Outcome, Query,
                    Rejected, SLOClass, SearchResult, SearchStats)
from ..core.policy import mask_words, roles_word_mask

#: ``ServeStats.summary()`` schema version (bump on breaking shape changes).
SUMMARY_SCHEMA = 2

_CLASS_ORDER = (SLOClass.INTERACTIVE, SLOClass.STANDARD, SLOClass.BULK)


def _pct(samples: List[float], p: float) -> float:
    if not samples:
        return 0.0
    return float(np.percentile(np.asarray(samples), p))


@dataclasses.dataclass
class ClassStats:
    """Per-SLO-class accounting block (one per class in
    :attr:`ServeStats.classes`)."""

    submitted: int = 0
    admitted: int = 0
    rejected: int = 0
    cancelled: int = 0
    completed: int = 0
    failed: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    queue_ms: List[float] = dataclasses.field(default_factory=list)
    latency_ms: List[float] = dataclasses.field(default_factory=list)

    @property
    def cache_hit_rate(self) -> float:
        n = self.cache_hits + self.cache_misses
        return self.cache_hits / n if n else 0.0

    def block(self) -> Dict[str, float]:
        """The stable per-class sub-block of ``summary()['classes']``."""
        return {
            "submitted": self.submitted, "admitted": self.admitted,
            "rejected": self.rejected, "cancelled": self.cancelled,
            "completed": self.completed, "failed": self.failed,
            "cache_hits": self.cache_hits,
            "cache_hit_rate": round(self.cache_hit_rate, 4),
            "p50_ms": _pct(self.latency_ms, 50),
            "p99_ms": _pct(self.latency_ms, 99),
        }


@dataclasses.dataclass
class ServeStats:
    """Serving-layer accounting for a scheduler run (benchmarks exp16/20).

    Attribute access is the live mutable form; :meth:`summary` renders the
    stable versioned schema consumers parse (``schema`` = 2)."""

    submitted: int = 0
    admitted: int = 0              # passed admission (== submitted w/o it)
    rejected: int = 0              # admission sheds (typed Rejected futures)
    completed: int = 0
    cancelled: int = 0             # futures cancelled before their flush
    failed: int = 0                # futures resolved with an exception
    batches_flushed: int = 0
    flush_full: int = 0            # flushed because max_batch was reached
    flush_timeout: int = 0         # flushed because a flush-by time passed
    flush_drain: int = 0           # flushed by drain()/close()
    flush_preempt: int = 0         # interactive deadline at risk: cut
                                   # bypassed the bulk backlog
    disjoint_flushes: int = 0      # device-aware cuts that deferred work
                                   # contending with in-flight flush slots
    batch_size_sum: int = 0
    batch_size_max: int = 0
    queue_depth_peak: int = 0
    # overlapping-flush accounting (max_inflight > 1, sharded stores):
    # flushes dispatched while at least one other was still executing,
    # and the highest number of concurrently executing flushes observed
    overlap_flushes: int = 0
    inflight_peak: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    queue_ms: List[float] = dataclasses.field(default_factory=list)
    latency_ms: List[float] = dataclasses.field(default_factory=list)
    search: SearchStats = dataclasses.field(default_factory=SearchStats)
    # per-SLO-class sub-blocks, keyed by SLOClass.label (always all three,
    # so the summary shape is stable regardless of traffic mix)
    classes: Dict[str, ClassStats] = dataclasses.field(
        default_factory=lambda: {c.label: ClassStats() for c in SLOClass})
    # admission rejection reasons -> count ("rate_limit" / "queue_depth" /
    # "deadline_infeasible")
    rejected_reasons: Dict[str, int] = dataclasses.field(default_factory=dict)
    # execution-path counts per flush: "sharded+packed" / "sharded" /
    # "batched+packed" / "batched" / "sequential" (which engine arm /
    # leftover strategy served the batch); cache hits count per request
    # under "cache"
    paths: Dict[str, int] = dataclasses.field(default_factory=dict)
    # latest per-device launch snapshot from a sharded store: device slot
    # -> cumulative host seconds inside its launches (not device time) /
    # kernel launches
    device_busy_s: Dict[int, float] = dataclasses.field(default_factory=dict)
    device_launches: Dict[int, int] = dataclasses.field(default_factory=dict)
    # background maintenance (LatticeCompactor hook): cycles run between
    # flushes, wall time spent, and the compactor's own counter deltas
    maintenance_runs: int = 0
    maintenance_ms: float = 0.0
    compaction: Dict[str, float] = dataclasses.field(default_factory=dict)

    def cls(self, slo: SLOClass) -> ClassStats:
        return self.classes[slo.label]

    def record_maintenance(self, elapsed_ms: float, counters) -> None:
        self.maintenance_runs += 1
        self.maintenance_ms += float(elapsed_ms)
        if isinstance(counters, dict):
            for k, v in counters.items():
                self.compaction[k] = self.compaction.get(k, 0) + v

    def record_path(self, path: str) -> None:
        self.paths[path] = self.paths.get(path, 0) + 1

    def record_reject(self, rej: Rejected) -> None:
        self.rejected += 1
        self.cls(rej.slo).rejected += 1
        self.rejected_reasons[rej.reason] = \
            self.rejected_reasons.get(rej.reason, 0) + 1

    def record_devices(self, device_stats: Dict[int, Dict[str, float]]
                       ) -> None:
        """Snapshot a sharded store's cumulative per-device launches: host
        seconds inside them and their count
        (:meth:`~repro.core.sharded.ShardedVectorStore.device_stats`)."""
        for slot, rec in device_stats.items():
            self.device_busy_s[slot] = float(rec["busy_s"])
            self.device_launches[slot] = int(rec["launches"])

    @property
    def avg_batch(self) -> float:
        return (self.batch_size_sum / self.batches_flushed
                if self.batches_flushed else 0.0)

    @property
    def cache_hit_rate(self) -> float:
        n = self.cache_hits + self.cache_misses
        return self.cache_hits / n if n else 0.0

    def latency_percentile(self, p: float) -> float:
        return _pct(self.latency_ms, p)

    @property
    def p50_ms(self) -> float:
        return self.latency_percentile(50)

    @property
    def p99_ms(self) -> float:
        return self.latency_percentile(99)

    def summary(self) -> Dict[str, object]:
        """The stable, versioned serving-stats schema (v2).

        Shape::

            {"schema": 2,
             "totals":  {submitted, admitted, rejected, completed,
                         cancelled, failed, batches, avg_batch, batch_max,
                         queue_depth_peak, overlap_flushes, inflight_peak,
                         cache_hits, cache_misses, cache_hit_rate,
                         p50_ms, p99_ms},
             "flush":   {full, timeout, drain, preempt, disjoint},
             "classes": {"interactive"|"standard"|"bulk": per-class block
                         (p50/p99, admitted/rejected/cancelled/completed,
                         cache hit rate) — always all three classes},
             "rejected_reasons": {reason: count},
             "paths":   {execution path: flush count},
             "devices": {slot: {busy_s, launches}},
             "maintenance": {runs, ms, compaction: {counter: delta}}}

        Consumers (``benchmarks/run.py --json`` derivations,
        ``scripts/check_perf.py`` inputs, exp16/exp18/exp19/exp20,
        ``examples/rag_serve.py``) read this one shape.
        """
        return {
            "schema": SUMMARY_SCHEMA,
            "totals": {
                "submitted": self.submitted, "admitted": self.admitted,
                "rejected": self.rejected, "completed": self.completed,
                "cancelled": self.cancelled, "failed": self.failed,
                "batches": self.batches_flushed, "avg_batch": self.avg_batch,
                "batch_max": self.batch_size_max,
                "queue_depth_peak": self.queue_depth_peak,
                "overlap_flushes": self.overlap_flushes,
                "inflight_peak": self.inflight_peak,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "cache_hit_rate": round(self.cache_hit_rate, 4),
                "p50_ms": self.p50_ms, "p99_ms": self.p99_ms,
            },
            "flush": {
                "full": self.flush_full, "timeout": self.flush_timeout,
                "drain": self.flush_drain, "preempt": self.flush_preempt,
                "disjoint": self.disjoint_flushes,
            },
            "classes": {label: cs.block()
                        for label, cs in sorted(self.classes.items())},
            "rejected_reasons": dict(sorted(self.rejected_reasons.items())),
            "paths": dict(sorted(self.paths.items())),
            "devices": {slot: {"busy_s": round(self.device_busy_s[slot], 4),
                               "launches": self.device_launches.get(slot, 0)}
                        for slot in sorted(self.device_busy_s)},
            "maintenance": {"runs": self.maintenance_runs,
                            "ms": round(self.maintenance_ms, 3),
                            "compaction": dict(sorted(
                                self.compaction.items()))},
        }


@dataclasses.dataclass(eq=False)
class _Request:
    query: Query
    t_submit: float
    flush_by: float                # cut-by time (class wait / deadline)
    future: "asyncio.Future"
    slots: Optional[frozenset] = None    # device slots its plan cover hits
    t_dispatch: float = 0.0        # stamped when its micro-batch is cut


# search_fn(store, queries: Sequence[Query]) -> List[SearchResult]
SearchFn = Callable[..., List[SearchResult]]


class MicroBatchScheduler:
    """Async SLO-aware continuous-batching front end for a vector store.

    ``submit`` never blocks: it enqueues and returns an ``asyncio.Future``
    resolved with that request's :data:`~repro.core.Outcome` — a
    :class:`SearchResult` (sorted authorized hits + per-query stats), or a
    typed :class:`Rejected` when admission sheds it.  The flusher coroutine
    (started lazily on first submit) owns batch cutting; each micro-batch's
    search runs on the default executor thread, so the event loop keeps
    accepting submissions *while a batch executes* — the backlog that
    accumulates during one search becomes the next flush's batch, which is
    what makes the batch size track the arrival rate.

    ``slo_aware`` (default True) enables per-class queues, strict-priority
    flush assembly, bulk wait stretching, and deadline preemption; False
    restores a single FIFO queue across classes (the PR 2–5 behavior — the
    exp20 baseline), while per-class *accounting* still happens either way.

    ``max_inflight`` bounds how many micro-batch searches may execute
    concurrently.  Values above 1 overlap flushes, which pays off on a
    :class:`~repro.core.sharded.ShardedVectorStore`; the device-aware cut
    policy (enabled automatically there, see the module docstring) keeps
    consecutive overlapped flushes on disjoint device slots.  All ``stats``
    mutation happens on the event loop (the executor only runs the
    search), so accounting stays race-free at any ``max_inflight``.
    """

    def __init__(self, store, *, max_batch: int = 32,
                 max_wait_ms: float = 2.0, default_k: int = 10,
                 default_efs: int = 50,
                 min_packed_batch: int = DEFAULT_MIN_PACKED_BATCH,
                 max_inflight: int = 1,
                 slo_aware: bool = True,
                 bulk_wait_factor: float = 8.0,
                 admission=None,
                 cache: Optional[AnswerCache] = None,
                 device_aware: Optional[bool] = None,
                 search_fn: Optional[SearchFn] = None,
                 stats: Optional[ServeStats] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 maintainer: Optional[Callable[[float], dict]] = None,
                 maintenance_budget_s: float = 0.02,
                 maintenance_interval_s: float = 0.25):
        assert max_batch >= 1, max_batch
        assert max_inflight >= 1, max_inflight
        assert bulk_wait_factor >= 1.0, bulk_wait_factor
        self.store = store
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self.default_k = int(default_k)
        self.default_efs = int(default_efs)
        self.min_packed_batch = int(min_packed_batch)
        self.max_inflight = int(max_inflight)
        self.slo_aware = bool(slo_aware)
        self.bulk_wait_factor = float(bulk_wait_factor)
        self.admission = admission
        self.cache = cache
        self.search_fn = search_fn
        self.stats = stats if stats is not None else ServeStats()
        self._clock = clock
        # background maintenance hook (LatticeCompactor.maintain or any
        # ``budget_s -> counter-delta dict`` callable): invoked between
        # flushes only while no search is in flight, so engine rebuilds
        # never race a query
        self.maintainer = maintainer
        self.maintenance_budget_s = float(maintenance_budget_s)
        self.maintenance_interval_s = float(maintenance_interval_s)
        self._last_maintain = self._clock()
        self._maintaining = False
        self._queues: Dict[SLOClass, List[_Request]] = {
            c: [] for c in SLOClass}
        self._wake: Optional[asyncio.Event] = None
        self._slot_free: Optional[asyncio.Event] = None
        self._idle: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._closed = False
        self._draining = False
        self._inflight = 0
        self._exec_tasks: set = set()
        # device-aware cut policy: requires the store to expose its
        # placement (slots_for_roles) and only matters with overlap
        self._slots_fn = getattr(store, "slots_for_roles", None)
        if device_aware is None:
            device_aware = (self._slots_fn is not None
                            and getattr(store, "mesh_size", 1) > 1
                            and self.max_inflight > 1)
        self._device_aware = bool(device_aware) and self._slots_fn is not None
        self._slot_cache: Dict[Tuple[int, ...], frozenset] = {}
        self._inflight_slots: Dict[int, frozenset] = {}
        self._next_flush_id = 0
        self._words_cache: Dict[Tuple[int, ...], np.ndarray] = {}
        self._flush_ms_ema = 0.0

    # ------------------------------------------------------------ submission
    def submit(self, query: Query) -> "asyncio.Future":
        """Enqueue one :class:`Query`; the future resolves to its
        :data:`~repro.core.Outcome` (``SearchResult`` or ``Rejected``)."""
        assert not self._closed, "scheduler is closed"
        assert isinstance(query, Query), (
            "submit takes a repro.core.Query (the positional "
            "submit(vector, role, k) shim was removed; use "
            "Query.single(vector, role, k=k))")
        loop = asyncio.get_running_loop()
        st = self.stats
        st.submitted += 1
        cs = st.cls(query.slo)
        cs.submitted += 1
        fut = loop.create_future()
        if self.admission is not None:
            rej = self.admission.admit(query, self._class_depths(),
                                       self._est_wait_ms())
            if rej is not None:
                st.record_reject(rej)
                fut.set_result(rej)
                return fut
        st.admitted += 1
        cs.admitted += 1
        if self.cache is not None:
            hits = self.cache.lookup(query.vector, self._query_words(query),
                                     query.k, query.efs,
                                     pwords=self._query_pwords(query))
            if hits is not None:
                st.cache_hits += 1
                cs.cache_hits += 1
                st.record_path("cache")
                st.queue_ms.append(0.0)
                st.latency_ms.append(0.0)
                cs.queue_ms.append(0.0)
                cs.latency_ms.append(0.0)
                st.completed += 1
                cs.completed += 1
                fut.set_result(SearchResult(hits=hits, path="cache"))
                return fut
            st.cache_misses += 1
            cs.cache_misses += 1
        now = self._clock()
        req = _Request(query=query, t_submit=now,
                       flush_by=now + self._wait_budget_s(query), future=fut,
                       slots=(self._slots_for(query)
                              if self._device_aware else None))
        bucket = query.slo if self.slo_aware else SLOClass.STANDARD
        self._queues[bucket].append(req)
        st.queue_depth_peak = max(st.queue_depth_peak, self._depth())
        if self._wake is None:
            self._wake = asyncio.Event()
        self._wake.set()
        if self._task is None or self._task.done():
            self._task = loop.create_task(self._run())
        return fut

    # --------------------------------------------------------- queue queries
    def _depth(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def _class_depths(self) -> Dict[SLOClass, int]:
        """Live backlog per *query* class (accurate even in FIFO mode,
        where all classes share one queue bucket)."""
        depths = {c: 0 for c in SLOClass}
        for q in self._queues.values():
            for r in q:
                depths[r.query.slo] += 1
        return depths

    def _est_wait_ms(self) -> float:
        """Queue-wait estimate for a new arrival: flushes ahead of it ×
        the flush-time EMA.  Conservatively 0 before the first flush."""
        if self._flush_ms_ema <= 0.0:
            return 0.0
        flushes_ahead = self._depth() / self.max_batch + self._inflight
        return flushes_ahead * self._flush_ms_ema

    def _wait_budget_s(self, query: Query) -> float:
        """Per-request flush-by budget: the class wait (bulk stretched by
        ``bulk_wait_factor``), tightened to half the deadline when one is
        set (the other half is left for the search itself)."""
        wait_ms = self.max_wait_ms
        if self.slo_aware and query.slo is SLOClass.BULK:
            wait_ms = self.max_wait_ms * self.bulk_wait_factor
        if query.deadline_ms is not None:
            wait_ms = min(wait_ms, 0.5 * query.deadline_ms)
        return wait_ms / 1e3

    def _query_words(self, query: Query) -> np.ndarray:
        words = self._words_cache.get(query.roles)
        if words is None:
            width = getattr(self.store, "mask_width", None)
            if width is None:
                width = mask_words(max(query.roles) + 1)
            words = roles_word_mask(query.roles, width=int(width))
            self._words_cache[query.roles] = words
        return words

    def _query_pwords(self, query: Query):
        """Compiled predicate words for the cache key (``None`` for
        unfiltered queries): filtered and unfiltered answers — and distinct
        predicates — must never share a cache entry."""
        if query.where is None:
            return None
        compile_where = getattr(self.store, "compile_where", None)
        if compile_where is None:
            raise ValueError(
                "filtered query submitted to a scheduler whose store has "
                "no predicate plane (compile_where)")
        rf = compile_where(query.where)
        if rf is None:
            return None
        return np.concatenate(rf).astype(np.uint32)

    def _slots_for(self, query: Query) -> frozenset:
        slots = self._slot_cache.get(query.roles)
        if slots is None:
            slots = frozenset(self._slots_fn(query.roles))
            self._slot_cache[query.roles] = slots
        return slots

    def _signal_idle(self) -> None:
        """Wake drain() when nothing is queued, in flight, or maintaining."""
        if (self._idle is not None and not self._depth()
                and self._inflight == 0 and not self._maintaining):
            self._idle.set()

    async def drain(self) -> None:
        """Flush everything queued, wait for in-flight batches to finish.
        Event-driven: parks on an idle event set by the last retiring batch
        (or maintenance cycle) instead of the former 0.5 ms poll loop."""
        self._draining = True
        if self._wake is not None:
            self._wake.set()
        if self._idle is None:
            self._idle = asyncio.Event()
        try:
            while self._depth() or self._inflight or self._maintaining:
                self._idle.clear()
                await self._idle.wait()
        finally:
            self._draining = False
        if self._task is not None and not self._task.done():
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    async def close(self) -> None:
        self._closed = True
        await self.drain()

    # ------------------------------------------------------------- flush loop
    async def _maybe_maintain(self, force: bool = False) -> None:
        """Run one maintenance cycle if the hook is set, nothing is in
        flight, and (unless ``force``) the interval elapsed.  The cycle runs
        on the executor, but no search dispatches while ``_maintaining`` is
        up — engine rebuilds never race a query."""
        if (self.maintainer is None or self._maintaining
                or self._inflight or self._draining):
            return
        now = self._clock()
        if not force and (now - self._last_maintain
                          < self.maintenance_interval_s):
            return
        self._maintaining = True
        try:
            loop = asyncio.get_running_loop()
            counters = await loop.run_in_executor(
                None, lambda: self.maintainer(self.maintenance_budget_s))
        finally:
            self._maintaining = False
            self._last_maintain = self._clock()
            self._signal_idle()
        self.stats.record_maintenance(
            (self._last_maintain - now) * 1e3, counters)

    def _next_flush_by(self) -> float:
        return min(r.flush_by for q in self._queues.values() for r in q)

    async def _run(self) -> None:
        while True:
            if not self._depth():
                # idle transition: one maintenance cycle, then park until
                # the next submit; drain() cancels us
                await self._maybe_maintain(force=True)
                if self._depth():
                    continue
                self._wake.clear()
                await self._wake.wait()
            # accumulate until full or the earliest flush-by time passes
            while (self._depth() and not self._draining
                   and self._depth() < self.max_batch):
                budget = self._next_flush_by() - self._clock()
                if budget <= 0:
                    break
                self._wake.clear()
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=budget)
                except asyncio.TimeoutError:
                    break
            # respect the overlap cap: park until an in-flight search
            # retires (max_inflight=1 degenerates to strictly serial
            # flushes, the pre-overlap behavior)
            while self._depth() and self._inflight >= self.max_inflight:
                if self._slot_free is None:
                    self._slot_free = asyncio.Event()
                self._slot_free.clear()
                await self._slot_free.wait()
            if self._depth():
                # between flushes, interval-gated: only fires when no search
                # is in flight (the previous flush has fully retired)
                await self._maybe_maintain()
                batch, reason = self._cut_batch()
                if batch:
                    self._dispatch(batch, reason)
            await asyncio.sleep(0)       # let submitters run between flushes

    # ------------------------------------------------------------- batch cut
    def _busy_slots(self) -> frozenset:
        if not self._inflight_slots:
            return frozenset()
        out: frozenset = frozenset()
        for s in self._inflight_slots.values():
            out = out | s
        return out

    def _cut_batch(self) -> Tuple[List[_Request], str]:
        """Assemble one micro-batch under the SLO policy.

        Strict priority: INTERACTIVE, then STANDARD, then BULK fills the
        remainder.  When an interactive request with a deadline is already
        past its flush-by time ("at risk"), the cut *preempts*: queued BULK
        work is excluded from this batch entirely so the deadline-sensitive
        answer is not co-scheduled behind a bulk scan.  When the
        device-aware policy is active and another flush is in flight, the
        cut further prefers requests whose device-slot sets don't intersect
        the busy slots — contenders wait for the next flush — except that a
        request past its flush-by time is never deferred.
        """
        now = self._clock()
        depth_before = self._depth()
        preempt_risk = self.slo_aware and any(
            r.flush_by <= now and r.query.deadline_ms is not None
            for r in self._queues[SLOClass.INTERACTIVE])
        bulk_bypassed = (preempt_risk
                         and bool(self._queues[SLOClass.BULK]))
        cands: List[_Request] = []
        for cls in _CLASS_ORDER:
            if cls is SLOClass.BULK and preempt_risk:
                continue
            cands.extend(self._queues[cls])
        disjoint_applied = False
        if self._device_aware and self._inflight > 0:
            busy = self._busy_slots()
            if busy:
                clear = [r for r in cands
                         if r.flush_by <= now or not r.slots
                         or not (r.slots & busy)]
                if clear and len(clear) < len(cands):
                    cands = clear
                    disjoint_applied = True
        batch = cands[:self.max_batch]
        if not batch:
            return [], "timeout"
        chosen = set(batch)        # _Request is eq=False → identity hash
        for cls in SLOClass:
            q = self._queues[cls]
            if q:
                self._queues[cls] = [r for r in q if r not in chosen]
        if bulk_bypassed:
            reason = "preempt"
        elif depth_before >= self.max_batch:
            reason = "full"
        elif self._draining:
            reason = "drain"
        else:
            reason = "timeout"
        if disjoint_applied:
            self.stats.disjoint_flushes += 1
        return batch, reason

    def _search(self, queries: Sequence[Query]) -> List[SearchResult]:
        if self.search_fn is not None:
            return self.search_fn(self.store, queries)
        return self.store.search(queries,
                                 min_packed_batch=self.min_packed_batch)

    def _dispatch(self, batch: List[_Request], reason: str) -> None:
        """Launch one cut micro-batch's search as a task.  The flusher loop
        continues immediately, so the next flush can dispatch while this
        one executes (bounded by ``max_inflight``); overlap accounting
        happens here, at dispatch time."""
        st = self.stats
        self._inflight += 1
        st.inflight_peak = max(st.inflight_peak, self._inflight)
        if self._inflight > 1:
            st.overlap_flushes += 1
        t0 = self._clock()
        for r in batch:
            r.t_dispatch = t0
        fid = self._next_flush_id
        self._next_flush_id += 1
        if self._device_aware:
            slots: frozenset = frozenset()
            for r in batch:
                if r.slots:
                    slots = slots | r.slots
            if slots:
                self._inflight_slots[fid] = slots
        task = asyncio.get_running_loop().create_task(
            self._execute(batch, reason, fid))
        # hold a strong reference until done (create_task alone is not
        # enough to keep a task alive across GC)
        self._exec_tasks.add(task)
        task.add_done_callback(self._exec_tasks.discard)

    async def _execute(self, batch: List[_Request], reason: str,
                       fid: int) -> None:
        """Run one dispatched micro-batch to completion and account it.
        Only the search itself leaves the event loop (executor thread);
        every ``stats`` mutation happens back on the loop, so overlapping
        flushes never race on accounting."""
        error: Optional[Exception] = None
        results: List = []
        try:
            qlist = [r.query for r in batch]

            def flush():
                # the root span of everything this flush does, opened on
                # the executor thread that runs it
                handoff_ms = (self._clock() - batch[0].t_dispatch) * 1e3
                with obs.span("serve.flush", flush=fid, batch=len(batch),
                              reason=reason, handoff_ms=handoff_ms):
                    return self._search(qlist)
            results = await asyncio.get_running_loop().run_in_executor(
                None, flush)
        except Exception as e:         # propagate to callers, keep serving
            error = e
        finally:
            self._inflight -= 1
            self._inflight_slots.pop(fid, None)
            if self._slot_free is not None:
                self._slot_free.set()
        with obs.span("serve.resolve", batch=len(batch)):
            self._resolve(batch, reason, results, error)
        self._signal_idle()

    def _resolve(self, batch: List[_Request], reason: str, results: List,
                 error: Optional[Exception]) -> None:
        """Account a finished micro-batch and resolve its futures (on the
        loop)."""
        st = self.stats
        # the batch was dequeued either way: flush counts stay honest
        t1 = self._clock()
        st.batches_flushed += 1
        st.batch_size_sum += len(batch)
        st.batch_size_max = max(st.batch_size_max, len(batch))
        setattr(st, f"flush_{reason}", getattr(st, f"flush_{reason}") + 1)
        flush_ms = (t1 - batch[0].t_dispatch) * 1e3
        self._flush_ms_ema = (flush_ms if self._flush_ms_ema <= 0.0
                              else 0.8 * self._flush_ms_ema + 0.2 * flush_ms)
        if error is None and results and isinstance(results[0], SearchResult):
            st.record_path(results[0].path)
            for res in results:
                st.search.merge(res.stats)
        from ..core import ShardedVectorStore
        if isinstance(self.store, ShardedVectorStore):
            st.record_devices(self.store.device_stats())
        # queue/latency samples are recorded only for requests actually
        # resolved here, so the percentile population and the ``completed``
        # (+``failed``) denominators agree; cancelled futures are counted
        # separately instead of skewing the latency distribution
        for i, r in enumerate(batch):
            cs = st.cls(r.query.slo)
            if r.future.done():          # caller cancelled before resolution
                st.cancelled += 1
                cs.cancelled += 1
                continue
            q_ms = (r.t_dispatch - r.t_submit) * 1e3
            l_ms = (t1 - r.t_submit) * 1e3
            st.queue_ms.append(q_ms)
            st.latency_ms.append(l_ms)
            cs.queue_ms.append(q_ms)
            cs.latency_ms.append(l_ms)
            if error is not None:
                st.failed += 1
                cs.failed += 1
                r.future.set_exception(error)
            else:
                st.completed += 1
                cs.completed += 1
                if self.cache is not None:
                    self.cache.store(r.query.vector,
                                     self._query_words(r.query),
                                     r.query.k, results[i].hits,
                                     efs=r.query.efs,
                                     pwords=self._query_pwords(r.query))
                r.future.set_result(results[i])


RequestLike = Union[Query, Tuple[np.ndarray, int, int]]


async def serve_requests(scheduler: MicroBatchScheduler,
                         requests: Sequence[RequestLike],
                         arrival_s: Optional[Sequence[float]] = None
                         ) -> List[Outcome]:
    """Submit a request stream and gather outcomes in submission order.

    ``requests`` is a sequence of :class:`Query` objects — or bare
    ``(vector, role, k)`` tuples, normalized here as a convenience — and
    ``arrival_s`` optionally gives each request's inter-arrival delay (an
    open-loop arrival process — exp16 uses exponential gaps, exp20
    adversarial mixed-priority traces).  Omitted, the whole stream is
    submitted back-to-back (closed-loop saturation).  Each element of the
    returned list is that request's :data:`~repro.core.Outcome`: a
    :class:`~repro.core.SearchResult`, or :class:`~repro.core.Rejected`
    when admission shed it.
    """
    futures = []
    try:
        for i, req in enumerate(requests):
            if (arrival_s is not None and i < len(arrival_s)
                    and arrival_s[i] > 0):
                await asyncio.sleep(arrival_s[i])
            if not isinstance(req, Query):
                q, role, k = req
                req = Query(vector=q, roles=(int(role),), k=int(k),
                            efs=scheduler.default_efs)
            futures.append(scheduler.submit(req))
        return list(await asyncio.gather(*futures))
    finally:
        # drain even when a request failed: resolves queued futures and
        # retires the flusher task instead of leaking it
        await scheduler.drain()
