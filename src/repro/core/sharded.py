"""Multi-device sharded lattice execution (DESIGN.md §Sharded Execution).

The batched engine (:mod:`~repro.core.batched`) amortizes one lattice sweep
across a query batch, but every ``l2_topk`` launch still lands on ONE
device.  The lattice's nodes are disjoint by construction, which makes them
embarrassingly placeable: this module spreads node shards across a
:class:`~repro.launch.mesh.DeviceMesh` and executes a batch's plan cover as
concurrent per-device launches, merging per-device partial top-k results
into the same global per-row heap — with the same k-th-distance bound
semantics — the batched engine already enforces.

Pieces:

  * :func:`place_shards` — greedy bin-packing of node shards onto mesh
    slots by the :func:`~repro.core.costmodel.shard_placement_cost`
    estimate; any node larger than a row threshold is split row-wise into
    per-device :class:`DeviceShard` slices first.
  * :class:`DeviceShard` — one device-pinned, contiguous row slice of a
    node's ScoreScan data (centered rows, auth and attribute words laid
    out for the kernel and committed to the shard's device by
    ``l2_topk.prepare_node``, as the parent
    :class:`~repro.ann.scorescan.ScoreScanIndex` keeps its own), scoring
    queries with the same kernel call — and bit-identical distances — as
    the parent.
  * :class:`ShardedVectorStore` — the drop-in store wrapper: the same
    ``search(queries)`` entry point, executed as per-device waves.  One
    single-worker executor per mesh slot acts as that device's launch
    stream; within a wave, launches on different devices run concurrently
    and the merged bounds propagate to the next round, so impure-node
    pruning keeps working across devices.  A ``mesh_size == 1`` mesh is
    degenerate: every call routes through the unchanged single-device
    ``VectorStore.search`` path.

Result parity: a shard launch returns the exact top-k of its row slice,
computed on the *parent node's* centering (slices keep the parent centroid,
so per-row distances are the same fp operations as the unsharded launch);
merging per-shard blocks through :class:`~repro.core.batched.BatchTopK`
therefore reproduces the single-device hits and distances bit-for-bit.
Bound-based skipping stays sound per shard — each slice carries its own
(tighter) centroid-radius bound around the parent centroid — so pruning can
only skip shards that provably cannot improve a row's top-k.
"""
from __future__ import annotations

import dataclasses
import math
from collections import defaultdict
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from .api import (DEFAULT_MIN_PACKED_BATCH, Query, QueryLike, SearchResult,
                  as_queries)
from .batched import (BatchTopK, _classify_waves, _filter_unauthorized,
                      _packed_leftover_rows, _prepare_batch,
                      _scan_leftovers_batched)
from .costmodel import ScanCostModel, shard_placement_cost
from .store import VectorStore

#: Placement key for the packed leftover shard (not a lattice node).
LEFTOVER_KEY = "__leftover__"


# --------------------------------------------------------------- placement
@dataclasses.dataclass(frozen=True)
class ShardAssignment:
    """One placed row range: node ``key`` rows ``[lo, hi)`` on mesh slot
    ``slot``, with its bin-packing weight ``cost``."""

    key: object                       # NodeKey, or LEFTOVER_KEY
    slot: int
    lo: int
    hi: int
    cost: float

    @property
    def rows(self) -> int:
        """Row count of this shard."""
        return self.hi - self.lo


@dataclasses.dataclass(frozen=True)
class Placement:
    """The full node→device assignment produced by :func:`place_shards`.

    ``assignments`` lists every placed shard; ``slot_cost[i]`` is slot
    ``i``'s total estimated per-launch cost (the bin-packing load);
    ``policy`` names the strategy that produced it (``"cost"`` or
    ``"round_robin"``)."""

    assignments: Tuple[ShardAssignment, ...]
    slot_cost: Tuple[float, ...]
    policy: str
    split_threshold: int

    def by_key(self) -> Dict[object, List[ShardAssignment]]:
        """Assignments grouped by node key, row ranges ascending."""
        out: Dict[object, List[ShardAssignment]] = defaultdict(list)
        for a in self.assignments:
            out[a.key].append(a)
        for shards in out.values():
            shards.sort(key=lambda a: a.lo)
        return dict(out)

    def imbalance(self) -> float:
        """max/mean slot load — 1.0 is a perfect pack."""
        costs = np.asarray(self.slot_cost, dtype=np.float64)
        mean = costs.mean() if len(costs) else 0.0
        return float(costs.max() / mean) if mean > 0 else 1.0


def place_shards(sizes: Dict[object, int], n_slots: int, dim: int, *,
                 policy: str = "cost",
                 split_threshold: Optional[int] = None,
                 model: Optional[ScanCostModel] = None) -> Placement:
    """Assign node shards to mesh slots.

    ``sizes`` maps node key → row count (zero-row entries are dropped).
    Nodes larger than ``split_threshold`` rows are first split row-wise into
    up to ``n_slots`` even chunks (per-shard auth words follow the rows), so
    one oversized node cannot serialize the mesh.  ``split_threshold=None``
    defaults to twice the ideal per-slot row load (so only genuinely
    outsized nodes split), with a floor of 256 rows.

    Policies:
      * ``"cost"`` (default) — greedy bin-packing: shards sorted by
        descending :func:`~repro.core.costmodel.shard_placement_cost`, each
        placed on the currently least-loaded slot.  Classic LPT: worst-case
        4/3 of optimal makespan, near-perfect on real lattices.
      * ``"round_robin"`` — shards assigned cyclically in key order,
        ignoring cost; the baseline policy exp18 compares against.
    """
    from ..launch.sharding import even_row_splits
    assert n_slots >= 1, n_slots
    assert policy in ("cost", "round_robin"), policy
    sizes = {k: int(n) for k, n in sizes.items() if int(n) > 0}
    total = sum(sizes.values())
    if split_threshold is None:
        split_threshold = max(256, math.ceil(2 * total / n_slots)) \
            if total else 256
    split_threshold = max(1, int(split_threshold))

    pieces: List[Tuple[object, int, int, float]] = []   # (key, lo, hi, cost)
    for key in sorted(sizes, key=str):
        n = sizes[key]
        if n > split_threshold:
            parts = min(n_slots, math.ceil(n / split_threshold))
            ranges = even_row_splits(n, parts)
        else:
            ranges = [(0, n)]
        for lo, hi in ranges:
            pieces.append((key, lo, hi,
                           shard_placement_cost(hi - lo, dim, model)))

    slot_cost = [0.0] * n_slots
    placed: List[ShardAssignment] = []
    if policy == "cost":
        # LPT greedy: heaviest shard first onto the least-loaded slot
        for key, lo, hi, cost in sorted(
                pieces, key=lambda p: (-p[3], str(p[0]), p[1])):
            slot = int(np.argmin(slot_cost))
            slot_cost[slot] += cost
            placed.append(ShardAssignment(key, slot, lo, hi, cost))
    else:
        for i, (key, lo, hi, cost) in enumerate(pieces):
            slot = i % n_slots
            slot_cost[slot] += cost
            placed.append(ShardAssignment(key, slot, lo, hi, cost))
    return Placement(assignments=tuple(placed), slot_cost=tuple(slot_cost),
                     policy=policy, split_threshold=split_threshold)


# ------------------------------------------------------------ device shards
class DeviceShard:
    """One device-pinned row slice of a node's ScoreScan data.

    The slice keeps the **parent node's centroid**: distances are computed
    on the parent's centered rows with the parent's query offset, so every
    per-row distance is the same fp value the unsharded kernel launch
    produces, and the merged top-k is bit-identical to single-device
    execution.  The shard's own pruning radius is recomputed from its rows
    (a tighter, still-sound centroid-radius bound).

    Satisfies the :class:`~repro.core.api.BatchEngine` protocol shape
    (``search_masked_batch`` / ``lower_bounds`` / ``ids`` / ``len``), which
    is what the wave executor drives.
    """

    def __init__(self, parent, device, slot: int, lo: int, hi: int,
                 key: object = None):
        from ..kernels.l2_topk import prepare_node
        self.key = key
        self.slot = int(slot)
        self.device = device
        self.lo, self.hi = int(lo), int(hi)
        self.ids = np.asarray(parent.ids[lo:hi])
        self.config = parent.config
        self.centroid = parent.centroid
        rows = parent._centered[lo:hi]
        self.auth_width = 1 if parent.auth_bits.ndim == 1 \
            else parent.auth_bits.shape[1]
        attr = parent.attr_bits
        self.pred_width = 0 if attr is None else attr.shape[1]
        if len(rows):
            norms2 = (rows * rows).sum(axis=1)
            self.radius = float(np.sqrt(norms2.max()))
            self.node = prepare_node(
                rows, parent.auth_bits[lo:hi],
                None if attr is None else attr[lo:hi], self.config, device)
        else:
            self.radius = 0.0
            self.node = None

    def __len__(self) -> int:
        return self.hi - self.lo

    def lower_bounds(self, qs: np.ndarray) -> np.ndarray:
        """Per-query centroid-radius lower bound over this slice's rows
        (same triangle-inequality form as the parent node, with the slice's
        own radius)."""
        if self.centroid is None or not len(self):
            return np.full(len(qs), np.inf, dtype=np.float32)
        dc = np.linalg.norm(qs - self.centroid, axis=1)
        return np.maximum(0.0, dc - self.radius) ** 2

    def search_masked_batch(self, qs: np.ndarray, k: int,
                            role_masks: np.ndarray,
                            bounds: Optional[np.ndarray] = None,
                            require: Optional[np.ndarray] = None,
                            forbid: Optional[np.ndarray] = None
                            ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact authorized top-k of this slice for a query batch: one
        ``l2_topk`` launch on this shard's device (operands committed there,
        query/mask/bound rows shipped per call).  Same contract as
        :meth:`~repro.ann.scorescan.ScoreScanIndex.search_masked_batch`;
        returned ids are external.  ``require``/``forbid`` (B, P) word rows
        evaluate the predicate conjunction in-kernel against this slice's
        pinned attribute rows."""
        b = len(qs)
        if not len(self):
            return (np.full((b, k), np.inf, np.float32),
                    np.full((b, k), -1, np.int64))
        import jax
        from ..ann.scorescan import read_back
        from ..kernels.l2_topk import l2_topk
        # identical fp preparation to the parent engine (bit-exact parity)
        qc = (np.asarray(qs, np.float32) - self.centroid).astype(np.float32)
        qd = jax.device_put(qc, self.device)
        md = jax.device_put(np.asarray(role_masks, np.uint32), self.device)
        bd = None if bounds is None else jax.device_put(
            np.asarray(bounds, np.float32), self.device)
        pkw = {}
        if require is not None or forbid is not None:
            if self.node.attr is None:
                raise ValueError(
                    "predicate rows against a shard with no attr plane")
            pkw = dict(
                require=jax.device_put(np.asarray(require, np.uint32),
                                       self.device),
                forbid=jax.device_put(np.asarray(forbid, np.uint32),
                                      self.device))
        d, i = l2_topk(qd, self.node, None, md, k,
                       bound=bd, config=self.config, **pkw)
        return read_back(d, i, self.ids)


# -------------------------------------------------------------- the store
class ShardedVectorStore:
    """A :class:`~repro.core.store.VectorStore` executed across a device
    mesh (DESIGN.md §Sharded Execution).

    Construction places every lattice-node engine (and the packed leftover
    shard, when the store has leftovers) onto mesh slots via
    :func:`place_shards` and pins each resulting :class:`DeviceShard`'s rows
    to its device.  ``search(queries)`` keeps the exact entry-point contract
    of ``VectorStore.search`` — same :class:`~repro.core.api.Query` in, same
    sorted authorized :class:`~repro.core.api.SearchResult` out, bit-identical
    hits/distances — but executes each wave as concurrent per-device
    launches (one single-worker executor per slot = one launch stream per
    device) with merged k-th-distance bounds propagating between rounds.

    ``mesh`` may be a :class:`~repro.launch.mesh.DeviceMesh`, an int (slot
    count over host devices), or an explicit device sequence.  A size-1 mesh
    is degenerate: ``search`` delegates to the wrapped store's unchanged
    single-device path, so batched/sequential/scheduler/dynamic behavior is
    byte-for-byte the PR-3 code.

    Attribute access not defined here (``plans``, ``policy``,
    ``authorized_mask``, ...) delegates to the wrapped store, so the wrapper
    is a drop-in for every serving layer (scheduler, RAGServer,
    ``warm_batch_shapes``).

    Thread safety: concurrent ``search`` calls are supported — per-call
    state (top-k buffers, stats) is private, and per-slot executors
    serialize launches per device while different devices serve different
    calls.  That is exactly what overlapping scheduler flushes exploit
    (DESIGN.md §Sharded Execution, "overlapping flushes").

    Placement is **static**: device shards snapshot the wrapped store's
    engines at construction.  Do not mutate the wrapped store afterwards
    (e.g. via ``DynamicStore``) — rebuild the wrapper after mutations;
    dynamic re-placement is future work (ROADMAP).
    """

    def __init__(self, store: VectorStore, mesh, *,
                 placement_policy: str = "cost",
                 split_threshold: Optional[int] = None,
                 cost_model: Optional[ScanCostModel] = None):
        from ..ann.scorescan import ScoreScanIndex
        self.store = store
        self.mesh = _as_mesh(mesh)
        dim = store.data.shape[1]

        bad = [k for k, e in store.engines.items()
               if not isinstance(e, ScoreScanIndex)]
        if bad:
            raise TypeError(
                f"sharded execution needs ScoreScan node engines "
                f"(scorescan_factory); non-scan engines at {bad[:3]}")

        sizes: Dict[object, int] = {k: len(e)
                                    for k, e in store.engines.items()}
        packed = store.pack_leftover_shard()
        if packed is not None:
            sizes[LEFTOVER_KEY] = len(packed)
        self.placement = place_shards(
            sizes, self.mesh.size, dim, policy=placement_policy,
            split_threshold=split_threshold, model=cost_model)

        self.node_shards: Dict[object, List[DeviceShard]] = {}
        self.leftover_shards: List[DeviceShard] = []
        for key, assigns in self.placement.by_key().items():
            parent = packed if key == LEFTOVER_KEY else store.engines[key]
            shards = [DeviceShard(parent, self.mesh[a.slot], a.slot,
                                  a.lo, a.hi, key=key) for a in assigns]
            if key == LEFTOVER_KEY:
                self.leftover_shards = shards
            else:
                self.node_shards[key] = shards

        # one single-worker executor per mesh slot: the device's launch
        # stream.  Slots sharing a physical device still get their own
        # stream (virtual meshes), which keeps placement/merge logic
        # identical on 1-device containers.
        self._executors = [
            ThreadPoolExecutor(max_workers=1,
                               thread_name_prefix=f"mesh-slot{i}")
            for i in range(self.mesh.size)]
        # per-slot launch accounting; each slot's entry is only mutated by
        # that slot's single worker thread, so no lock is needed.
        # device_busy_s is host seconds inside launches (the slot's
        # scan.launch spans summed), not device time
        self.device_busy_s: List[float] = [0.0] * self.mesh.size
        self.device_launches: List[int] = [0] * self.mesh.size
        self._closed = False

    # ------------------------------------------------------------- plumbing
    def __getattr__(self, name):
        # delegation to the wrapped store (plans, policy, masks, caches...);
        # only called for attributes this wrapper does not define
        if name == "store":          # guard: never recurse pre-__init__
            raise AttributeError(name)
        return getattr(self.store, name)

    @property
    def mesh_size(self) -> int:
        """Number of mesh slots this store executes across."""
        return self.mesh.size

    def device_shards(self):
        """Iterate every placed :class:`DeviceShard` (nodes + leftovers) —
        used by jit warm-up to trace each device's kernel signatures."""
        for shards in self.node_shards.values():
            yield from shards
        yield from self.leftover_shards

    def device_stats(self) -> Dict[int, Dict[str, float]]:
        """Cumulative per-slot launches: ``busy_s``, host seconds inside
        the slot's launches (its ``scan.launch`` spans), and counts."""
        return {i: {"busy_s": self.device_busy_s[i],
                    "launches": float(self.device_launches[i])}
                for i in range(self.mesh.size)}

    def slots_for_roles(self, roles) -> frozenset:
        """Mesh slots a query under this role set will touch: the slots
        holding shards of its plan cover's nodes, plus the packed-leftover
        slots when the plan has leftover blocks.  This is what the
        scheduler's device-aware cut policy keys on (DESIGN.md §SLO-Aware
        Serving): two queries with disjoint slot sets can execute in
        overlapped flushes without contending on any launch stream."""
        plan = self.store.plan_for_roles(tuple(roles))
        slots = set()
        for key in plan.nodes:
            for sh in self.node_shards.get(key, ()):
                slots.add(sh.slot)
        if plan.leftover_blocks:
            for sh in self.leftover_shards:
                slots.add(sh.slot)
        return frozenset(slots)

    def close(self) -> None:
        """Shut down the per-slot executors (idempotent)."""
        if not self._closed:
            self._closed = True
            for ex in self._executors:
                ex.shutdown(wait=True)

    def _submit(self, shard: DeviceShard, qs: np.ndarray, k: int,
                role_rows: np.ndarray, bounds: np.ndarray,
                require: Optional[np.ndarray] = None,
                forbid: Optional[np.ndarray] = None):
        """Enqueue one shard launch on its slot's stream; returns a future
        resolving to the shard's ``(dists, ids)`` block."""
        slot = shard.slot
        # the launch runs on the slot's thread, in the caller's span tree
        parent = obs.current()

        def run():
            sp = obs.span("scan.launch", slot=slot, n=len(shard), b=len(qs),
                          k=k)
            try:
                with obs.within(parent), sp:
                    return shard.search_masked_batch(qs, k, role_rows,
                                                     bounds=bounds,
                                                     require=require,
                                                     forbid=forbid)
            finally:
                self.device_busy_s[slot] += sp.seconds
                self.device_launches[slot] += 1
        return self._executors[slot].submit(run)

    # ----------------------------------------------------------- entry point
    def search(self, queries: QueryLike, *,
               packed: Optional[bool] = None,
               min_packed_batch: int = DEFAULT_MIN_PACKED_BATCH
               ) -> List[SearchResult]:
        """Authorized top-k for a query batch across the mesh.

        Contract-identical to :meth:`~repro.core.store.VectorStore.search`
        (heterogeneous per-query k, multi-role unions, ``packed`` leftover
        strategy selection) with ``path`` reported as ``"sharded"`` /
        ``"sharded+packed"``.  On a size-1 mesh this is a pure delegation to
        the wrapped store — the degenerate-mesh guarantee.
        """
        queries = as_queries(queries)
        if not queries:
            return []
        if self.mesh.size == 1:
            return self.store.search(queries, packed=packed,
                                     min_packed_batch=min_packed_batch)
        return self._execute(queries, packed, min_packed_batch)

    # -------------------------------------------------------- sharded engine
    def _execute(self, queries: Sequence[Query], packed: Optional[bool],
                 min_packed_batch: int) -> List[SearchResult]:
        store = self.store
        b = len(queries)
        with obs.span("search.plan", rows=b):
            (qs, ks, kmax, role_sets, plans, row_masks, role_bits,
             stats_rows, pred_rows, pred_masks) = _prepare_batch(store,
                                                                 queries)
            pure_rows, impure_rows, sizes_cache = _classify_waves(
                store, plans, role_sets, row_masks, stats_rows)
        topk = BatchTopK(b, kmax, ks=ks)

        # mirror the batched engine's path semantics: "+packed" only when a
        # packed shard actually exists (a leftover-free store reports plain
        # "sharded" even under packed=True)
        use_packed = bool(self.leftover_shards) and (
            packed is True or (packed is None and b >= min_packed_batch))
        path = "sharded+packed" if use_packed else "sharded"
        with obs.span("search.leftovers", path=path):
            if use_packed:
                rows = _packed_leftover_rows(store, plans, stats_rows)
                if len(rows):
                    req = forb = None
                    if pred_rows is not None:
                        req, forb = pred_rows[0][rows], pred_rows[1][rows]
                    futs = [self._submit(s, qs[rows], topk.k,
                                         role_bits[rows],
                                         np.full(len(rows), np.inf,
                                                 np.float32),
                                         require=req, forbid=forb)
                            for s in self.leftover_shards]
                    for fut in futs:
                        d, ids = fut.result()
                        # defense in depth, as on the single-shard path
                        with obs.span("search.merge", rows=len(rows)):
                            _filter_unauthorized(d, ids, rows, row_masks)
                            topk.push_rows(rows, d, ids)
            else:
                _scan_leftovers_batched(store, qs, plans, topk, stats_rows,
                                        pred_masks=pred_masks)

        for groups, impure in ((pure_rows, False), (impure_rows, True)):
            with obs.span("search.wave", kind="impure" if impure else "pure",
                          nodes=len(groups)):
                self._wave(groups, impure, qs, kmax, role_bits, role_sets,
                           row_masks, sizes_cache, topk, stats_rows,
                           pred_rows)
        with obs.span("search.merge", rows=b):
            items = topk.items()
            return [SearchResult(hits=items[i][:int(ks[i])],
                                 stats=stats_rows[i], path=path)
                    for i in range(b)]

    def _wave(self, groups: Dict, impure: bool, qs, kmax, role_bits,
              role_sets, row_masks, sizes_cache, topk, stats_rows,
              pred_rows=None) -> None:
        """One purity wave, executed as per-device rounds.

        Every (node, row-slice) shard touched by the wave joins its slot's
        queue, nearest-first by that shard's min lower bound.  Each round
        takes the head of every non-empty queue, prunes rows against their
        *current* k-th distance, launches the survivors concurrently (one
        launch per device stream), then merges all result blocks — so bound
        updates propagate between rounds exactly like the batched engine's
        node-sequential sweep, and across devices.

        Stats mirror the batched engine's logical accounting: data-touched /
        authorized counters per (row, node) regardless of row-splitting;
        a row counts a phase-2 skip when *no* shard of a node was launched
        for it (the schedule-dependent counters stay schedule-dependent,
        as documented in DESIGN.md §Batched Execution).
        """
        store = self.store
        if not groups:
            return
        # logical per-(row, node) accounting — identical to the batched path
        for key, rows in groups.items():
            eng = store.engines[key]
            for qi in rows:
                st = stats_rows[qi]
                if impure:
                    total, auth = sizes_cache[(key, role_sets[qi])]
                    st.impure_visits += 1
                else:
                    total = auth = len(eng)
                st.data_touched += total
                st.data_authorized_touched += auth

        queues: Dict[int, List] = defaultdict(list)
        for key, rows in groups.items():
            rows = np.asarray(rows)
            for shard in self.node_shards[key]:
                lbs = shard.lower_bounds(qs[rows])
                queues[shard.slot].append(
                    (float(lbs.min()) if len(lbs) else np.inf,
                     shard, key, rows, lbs))
        for q in queues.values():
            q.sort(key=lambda t: t[0])

        launched: Dict[object, set] = defaultdict(set)
        while any(queues.values()):
            round_items = [queues[s].pop(0)
                           for s in sorted(queues) if queues[s]]
            futs = []
            for _, shard, key, rows, lbs in round_items:
                kth = topk.kth(rows)
                active = lbs <= kth
                if not active.any():
                    continue
                act = rows[active]
                launched[key].update(int(qi) for qi in act)
                req = forb = None
                if pred_rows is not None:
                    req, forb = pred_rows[0][act], pred_rows[1][act]
                futs.append((key, act, self._submit(
                    shard, qs[act], kmax, role_bits[act], kth[active],
                    require=req, forbid=forb)))
            for key, act, fut in futs:
                d, ids = fut.result()
                with obs.span("search.merge", rows=len(act)):
                    if impure:
                        _filter_unauthorized(d, ids, act, row_masks)
                    topk.push_rows(act, d, ids)
        for key, rows in groups.items():
            for qi in rows:
                if int(qi) not in launched[key]:
                    stats_rows[qi].phase2_skipped += 1
                    if not impure:
                        stats_rows[qi].impure_visits += 1   # skip opportunity


def _as_mesh(mesh):
    """Normalize ``mesh`` (DeviceMesh | int | device sequence) to a
    :class:`~repro.launch.mesh.DeviceMesh`."""
    from ..launch.mesh import DeviceMesh
    if isinstance(mesh, DeviceMesh):
        return mesh
    if isinstance(mesh, (int, np.integer)):
        return DeviceMesh.host(int(mesh))
    return DeviceMesh(devices=tuple(mesh))


def shard_store(store: VectorStore, mesh, *, placement_policy: str = "cost",
                split_threshold: Optional[int] = None,
                cost_model: Optional[ScanCostModel] = None
                ) -> ShardedVectorStore:
    """Place a built store's node engines across ``mesh`` and return the
    sharded drop-in (see :class:`ShardedVectorStore`).  ``mesh`` may be a
    :class:`~repro.launch.mesh.DeviceMesh`, an int slot count, or a device
    sequence."""
    return ShardedVectorStore(store, mesh, placement_policy=placement_policy,
                              split_threshold=split_threshold,
                              cost_model=cost_model)
