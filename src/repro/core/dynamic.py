"""Dynamic workloads (paper Appendix I): inserts, deletes, policy updates.

Every vector belongs to exactly one exclusive block; the container map Φ
records which lattice nodes (and the leftover pool) physically hold that
block. Updates touch only Φ(block):

  insert(v, tau)      — append v to each container of N^ex(tau); a new tau
                        creates a fresh leftover block (metadata only).
  delete(v)           — tombstone v in each container.
  grant/revoke(v, r)  — move v between blocks tau → tau∪{r} / tau∖{r};
                        only the symmetric difference of containers changes.

Engines: capability-checked against the :mod:`repro.core.api` protocols —
:class:`MutableEngine` (HNSW) grows in place via native incremental insert
and marks deletes with ``tombstone``; everything else (ExactIndex /
ScoreScan) rebuilds its (small) node arrays, with per-vector auth bits
recomputed for :class:`MaskedEngine` rebuilds.  Queries route through the
unified entry point ``store.search`` — so ScoreScan-backed dynamic stores
take the batched kernel path — with a tombstone-aware over-fetch: ``k`` is
padded only by tombstones *authorized for the querying role set* (an
out-of-role delete can never surface in this plan cover, so it costs
nothing), and tombstoned ids are filtered from the result.

Correctness (every authorized vector reachable; no leaks) is preserved
immediately; *optimality* drifts and is restored lazily — when a node's
size or impurity drifts past ``slack``, re-run copy/merge locally (here:
flag the node for rebuild; full EffVEDA re-run on large policy changes per
Appendix I).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

import numpy as np

from .api import (MaskedEngine, MutableEngine, Query, SearchResult,
                  roles_word_mask)
from .policy import AccessPolicy, Role, RoleSet
from .queryplan import Plan, build_all_plans
from .store import VectorStore
from .costmodel import HNSWCostModel
from ..ann.exact import ExactIndex
from ..ann.scorescan import ScoreScanIndex


class DynamicStore:
    """Mutable wrapper over a built VectorStore (Appendix I semantics)."""

    def __init__(self, store: VectorStore, cost_model: HNSWCostModel,
                 k: int = 10, slack: float = 0.3, result_cache=None):
        self.store = store
        self.cm = cost_model
        self.k = k
        self.slack = slack
        # optional auth-aware answer cache (core/cache.py): consulted by
        # ``search`` and invalidated *precisely* by each mutation — the
        # mutated block's role combination names exactly which cached
        # answers could observe the change (DESIGN.md §SLO-Aware Serving)
        self.result_cache = result_cache
        policy = store.policy
        # mutable policy state
        self.block_roles: List[RoleSet] = list(policy.block_roles)
        self.block_members: List[List[int]] = [list(m) for m in
                                               policy.block_members]
        self.vec_block: Dict[int, int] = {}
        for b, members in enumerate(self.block_members):
            for v in members:
                self.vec_block[int(v)] = b
        self.data: List[np.ndarray] = [row for row in store.data]
        # amortized growth buffer behind ``store.data``: inserts write into
        # spare capacity and re-expose a prefix view, so per-insert cost is
        # O(d) amortized instead of the former O(N·d) full-corpus vstack.
        # Capacity doubles on exhaustion; ``data_reallocs`` counts doublings
        # (≤ log2(total inserts) + 1 — asserted in tests/test_compaction.py).
        self._data_buf = np.ascontiguousarray(store.data, np.float32)
        self._data_len = len(self._data_buf)
        self.data_reallocs = 0
        store.data = self._data_buf[:self._data_len]
        # predicate-word plane growth buffer (same scheme, kept row-aligned
        # with ``store.data``); ``None`` when the store has no plane
        self._attr_buf: Optional[np.ndarray] = None
        if store.attr_words is not None:
            self._attr_buf = np.ascontiguousarray(store.attr_words,
                                                  np.uint32)
            store.attr_words = self._attr_buf[:self._data_len]
        # per-block leftover growth buffers (same scheme); the store's
        # leftover_ids/leftover_vectors entries stay prefix views into these
        self._left_ids_buf: Dict[int, np.ndarray] = {}
        self._left_vecs_buf: Dict[int, np.ndarray] = {}
        self._left_len: Dict[int, int] = {}
        self.leftover_reallocs = 0
        self.tombstones: Set[int] = set()
        # role combination each tombstoned vector carried when deleted:
        # the over-fetch pad intersects these with the querying role set
        self.tombstone_roles: Dict[int, RoleSet] = {}
        self.dirty_nodes: Set = set()
        self._base_sizes = {key: len(store.engines[key].ids)
                            for key in store.engines}

    # ------------------------------------------------------------- internals
    def attach_cache(self, cache) -> None:
        """Attach an :class:`~repro.core.AnswerCache` (cleared first — it
        may hold answers from before this store's mutations)."""
        cache.clear()
        self.result_cache = cache

    def _cache_words(self, roles: Sequence[Role]) -> np.ndarray:
        return roles_word_mask(sorted(set(int(r) for r in roles)),
                               width=self.store.mask_width)

    def _cache_mutated(self, tau: RoleSet) -> None:
        """Precise invalidation for an insert or a grant/revoke move: drop
        cached answers whose role-mask words intersect the mutated
        combination.  Sufficiency: a vector in block ``tau`` is authorized
        for exactly the roles in ``tau``, so an answer under a disjoint
        role set can neither gain nor lose it."""
        if self.result_cache is not None and tau:
            self.result_cache.invalidate_words(self._cache_words(tau))

    def _cache_deleted(self, vid: int) -> None:
        """Precise invalidation for a delete: removing a vector only
        changes answers that surfaced it."""
        if self.result_cache is not None:
            self.result_cache.invalidate_id(vid)

    def _block_key(self, tau: RoleSet) -> int:
        for b, t in enumerate(self.block_roles):
            if t == tau:
                return b
        # previously unseen combination: fresh leftover block (App. I)
        self.block_roles.append(tau)
        self.block_members.append([])
        b = len(self.block_roles) - 1
        self.store.leftover_ids[b] = np.empty(0, np.int64)
        self.store.leftover_vectors[b] = np.empty(
            (0, self.store.data.shape[1]), np.float32)
        for r in tau:
            plan = self.store.plans[r]
            self.store.plans[r] = Plan(
                nodes=plan.nodes,
                leftover_blocks=tuple(sorted(set(plan.leftover_blocks)
                                             | {b})))
        return b

    def _containers(self, b: int):
        nodes = [key for key, node in self.store.lattice.nodes.items()
                 if b in node.blocks]
        in_leftover = b in self.store.leftover_ids
        return nodes, in_leftover

    def _append_data(self, vec: np.ndarray,
                     attr_row: Optional[np.ndarray] = None) -> None:
        """Append one row to the corpus via the growth buffer (amortized
        O(d)); ``store.data`` is re-exposed as a prefix view.  When the
        store carries a predicate plane, the aligned attribute row rides
        along (``None`` → all-zero words, which fail every nonzero
        require)."""
        if self._data_len == len(self._data_buf):
            cap = max(8, 2 * len(self._data_buf))
            new = np.empty((cap, self._data_buf.shape[1]), np.float32)
            new[:self._data_len] = self._data_buf
            self._data_buf = new
            self.data_reallocs += 1
            if self._attr_buf is not None:
                anew = np.zeros((cap, self._attr_buf.shape[1]), np.uint32)
                anew[:self._data_len] = self._attr_buf[:self._data_len]
                self._attr_buf = anew
        self._data_buf[self._data_len] = vec
        if self._attr_buf is not None:
            self._attr_buf[self._data_len] = (
                0 if attr_row is None else np.asarray(attr_row, np.uint32))
        self._data_len += 1
        self.store.data = self._data_buf[:self._data_len]
        if self._attr_buf is not None:
            self.store.attr_words = self._attr_buf[:self._data_len]

    def _attr_row_of(self, vid: int) -> Optional[np.ndarray]:
        """The (P,) attribute-word row of ``vid``, ``None`` without a
        plane."""
        if self.store.attr_words is None:
            return None
        return self.store.attr_words[int(vid)]

    def _encode_attrs(self, attrs) -> Optional[np.ndarray]:
        """Normalize an insert's ``attrs`` (None | dict via the store's
        schema | pre-encoded (P,) words) to a word row."""
        if attrs is None:
            return None
        if isinstance(attrs, dict):
            if self.store.pred_schema is None:
                raise ValueError(
                    "insert with attribute dict but the store has no "
                    "pred_schema")
            return self.store.pred_schema.encode(attrs)
        return np.asarray(attrs, np.uint32)

    def _adopt_leftover_buffers(self, b: int, d: int) -> None:
        """Move block ``b``'s leftover arrays into growth buffers (lazy —
        first mutation only; seed blocks never touched stay as built)."""
        ids0 = self.store.leftover_ids.get(b, np.empty(0, np.int64))
        vecs0 = self.store.leftover_vectors.get(
            b, np.empty((0, d), np.float32))
        cap = max(8, 2 * len(ids0))
        ib = np.empty(cap, np.int64)
        vb = np.empty((cap, d), np.float32)
        ib[:len(ids0)] = ids0
        vb[:len(ids0)] = vecs0
        self._left_ids_buf[b] = ib
        self._left_vecs_buf[b] = vb
        self._left_len[b] = len(ids0)

    def _expose_leftover(self, b: int) -> None:
        n = self._left_len[b]
        self.store.leftover_ids[b] = self._left_ids_buf[b][:n]
        self.store.leftover_vectors[b] = self._left_vecs_buf[b][:n]

    def _append_leftover(self, b: int, vid: int, vec: np.ndarray) -> None:
        if b not in self._left_len:
            self._adopt_leftover_buffers(b, len(vec))
        n = self._left_len[b]
        if n == len(self._left_ids_buf[b]):
            cap = max(8, 2 * n)
            ib = np.empty(cap, np.int64)
            vb = np.empty((cap, self._left_vecs_buf[b].shape[1]), np.float32)
            ib[:n] = self._left_ids_buf[b][:n]
            vb[:n] = self._left_vecs_buf[b][:n]
            self._left_ids_buf[b] = ib
            self._left_vecs_buf[b] = vb
            self.leftover_reallocs += 1
        self._left_ids_buf[b][n] = np.int64(vid)
        self._left_vecs_buf[b][n] = vec
        self._left_len[b] = n + 1
        self._expose_leftover(b)

    def _drop_leftover(self, b: int, vid: int) -> None:
        if b not in self._left_len:
            self._adopt_leftover_buffers(
                b, self.store.leftover_vectors[b].shape[1])
        n = self._left_len[b]
        ids = self._left_ids_buf[b][:n]
        keep = ids != np.int64(vid)
        m = int(keep.sum())
        if m != n:
            # compact survivors into the buffer prefix (fancy indexing copies
            # first, so the in-place prefix write is safe)
            self._left_ids_buf[b][:m] = ids[keep]
            self._left_vecs_buf[b][:m] = self._left_vecs_buf[b][:n][keep]
            self._left_len[b] = m
        self._expose_leftover(b)

    def _discard_leftover_block(self, b: int) -> None:
        """Remove block ``b`` from the leftover pool entirely (compaction
        folds it into a lattice node)."""
        self.store.leftover_ids.pop(b, None)
        self.store.leftover_vectors.pop(b, None)
        self._left_ids_buf.pop(b, None)
        self._left_vecs_buf.pop(b, None)
        self._left_len.pop(b, None)

    @staticmethod
    def _auth_row(eng, tau: RoleSet):
        """The auth-mask row for role combination ``tau`` in the layout of
        ``eng.auth_bits``: a uint32 scalar for single-word engines, a ``(W,)``
        word array for multi-word ones (DESIGN.md §Role Masks).  A role that
        does not fit the engine's mask width is a hard error — never an
        aliased bit."""
        if eng.auth_bits.ndim == 1:
            return roles_word_mask(tau, width=1)[0]
        return roles_word_mask(tau, width=eng.auth_bits.shape[1])

    def _engine_with(self, eng, vid: int, vec: np.ndarray, tau: RoleSet):
        """Rebuild a non-mutable engine with one extra row.  MaskedEngine
        rebuilds carry per-vector auth mask words: existing rows keep
        theirs, the new row's words come from its role combination ``tau``."""
        data = np.vstack([eng.data, vec[None]])
        ids = np.append(eng.ids, np.int64(vid))
        if isinstance(eng, MaskedEngine):
            row = self._auth_row(eng, tau)
            auth = (np.append(eng.auth_bits, row)
                    if eng.auth_bits.ndim == 1
                    else np.vstack([eng.auth_bits, row[None]]))
            kw = {}
            if eng.attr_bits is not None:
                arow = self._attr_row_of(vid)
                if arow is None:
                    arow = np.zeros(eng.attr_bits.shape[1], np.uint32)
                kw["attr_bits"] = np.vstack(
                    [eng.attr_bits, np.asarray(arow, np.uint32)[None]])
            return type(eng)(data, ids=ids,
                             auth_bits=auth.astype(np.uint32),
                             config=eng.config, **kw)
        return type(eng)(data, ids=ids)

    def _engine_without(self, eng, vid: int):
        """Rebuild a non-mutable engine with row ``vid`` physically removed
        (grants/revocations: a stale copy in a container of the *old* block
        would otherwise surface for the revoked role via pure-node searches,
        which skip the exact-mask post-filter)."""
        keep = eng.ids != np.int64(vid)
        if isinstance(eng, MaskedEngine):
            kw = {} if eng.attr_bits is None else \
                dict(attr_bits=eng.attr_bits[keep])
            return type(eng)(eng.data[keep], ids=eng.ids[keep],
                             auth_bits=eng.auth_bits[keep].astype(np.uint32),
                             config=eng.config, **kw)
        return type(eng)(eng.data[keep], ids=eng.ids[keep])

    def _sync_policy(self, with_roles: bool = True) -> None:
        kw = dict(block_members=tuple(np.asarray(m, np.int64)
                                      for m in self.block_members))
        if with_roles:
            kw["block_roles"] = tuple(self.block_roles)
        self.store.policy = dataclasses.replace(self.store.policy, **kw)
        self.store.lattice.policy = self.store.policy
        self.store.lattice.block_sizes = self.store.policy.block_sizes
        # masks, multi-role plan covers, and the packed leftover shard all
        # derive from the state just mutated
        self.store.invalidate_caches()

    # ------------------------------------------------------------ operations
    def insert(self, vec: np.ndarray, tau: RoleSet, attrs=None) -> int:
        vid = len(self.data)
        vec = np.asarray(vec, np.float32)
        self.data.append(vec)
        arow = self._encode_attrs(attrs)
        self._append_data(vec, attr_row=arow)
        if self.store.attr_words is not None:
            self.store.note_attr_rows(self.store.attr_words[vid], sign=1)
        tau = frozenset(tau)
        b = self._block_key(tau)
        self.block_members[b].append(vid)
        self.vec_block[vid] = b
        nodes, in_left = self._containers(b)
        for key in nodes:
            eng = self.store.engines[key]
            if isinstance(eng, MutableEngine):     # HNSW native incremental
                if isinstance(eng, MaskedEngine):  # auth words ride along
                    eng.insert(vid, vec, auth_bits=self._auth_row(eng, tau),
                               attr_bits=self._attr_row_of(vid))
                else:
                    eng.insert(vid, vec)
            else:                                  # exact/scan: rebuild
                self.store.engines[key] = self._engine_with(eng, vid, vec,
                                                            tau)
            self.dirty_nodes.add(key)
        if in_left or not nodes:
            self._append_leftover(b, vid, vec)
        # membership bookkeeping for impurity/purity checks
        self._sync_policy()
        # the new vector can enter any cached top-k whose roles see ``tau``
        self._cache_mutated(tau)
        return vid

    def delete(self, vid: int) -> None:
        vid = int(vid)
        self.tombstones.add(vid)
        if self.store.attr_words is not None:
            self.store.note_attr_rows(self.store.attr_words[vid], sign=-1)
        b = self.vec_block[vid]
        self.tombstone_roles[vid] = self.block_roles[b]
        self.block_members[b] = [v for v in self.block_members[b]
                                 if v != vid]
        nodes, in_left = self._containers(b)
        if in_left:
            self._drop_leftover(b, vid)
        # engines keep the row; queries filter tombstones (cheap), nodes
        # marked dirty for lazy re-optimization
        for key in nodes:
            eng = self.store.engines[key]
            if isinstance(eng, MutableEngine):
                eng.tombstone(vid)
        self.dirty_nodes.update(nodes)
        self._sync_policy(with_roles=False)
        self._cache_deleted(vid)

    def grant(self, vid: int, r: Role) -> None:
        self._move(vid, lambda tau: frozenset(tau | {r}))

    def revoke(self, vid: int, r: Role) -> None:
        self._move(vid, lambda tau: frozenset(tau - {r}))

    def _move(self, vid: int, fn) -> None:
        vid = int(vid)
        vec = self.data[vid]
        old_tau = self.block_roles[self.vec_block[vid]]
        new_tau = fn(old_tau)
        if new_tau == old_tau:
            return
        assert new_tau, "revoking the last role would orphan the vector"
        old_nodes, _ = self._containers(self.vec_block[vid])
        self.delete(vid)
        self.tombstones.discard(vid)
        self.tombstone_roles.pop(vid, None)
        if self.store.attr_words is not None:
            # the row stays live: undo delete()'s population decrement
            self.store.note_attr_rows(self.store.attr_words[vid], sign=1)
        # re-insert under the new combination, reusing the same id
        b = self._block_key(new_tau)
        self.block_members[b].append(vid)
        self.vec_block[vid] = b
        nodes, in_left = self._containers(b)
        for key in nodes:
            eng = self.store.engines[key]
            if isinstance(eng, MutableEngine):
                # auth words ride along atomically — the row must never be
                # live with stale/zero words (insert() handles the
                # pre-existing-row case by refreshing in place)
                if isinstance(eng, MaskedEngine):
                    eng.insert(vid, vec,
                               auth_bits=self._auth_row(eng, new_tau),
                               attr_bits=self._attr_row_of(vid))
                else:
                    eng.insert(vid, vec)   # clears the tombstone mark too
            elif vid in set(int(i) for i in eng.ids):
                # old and new block share this container: refresh the row's
                # auth words in place so the in-kernel filter tracks new_tau
                # (a ScoreScan node also drops its stale device copy)
                if isinstance(eng, ScoreScanIndex):
                    eng.set_auth_words(vid, self._auth_row(eng, new_tau))
                elif isinstance(eng, MaskedEngine):
                    eng.auth_bits[eng.ids == np.int64(vid)] = \
                        self._auth_row(eng, new_tau)
            else:
                self.store.engines[key] = self._engine_with(eng, vid, vec,
                                                            new_tau)
            self.dirty_nodes.add(key)
        # purge the stale copy from old-block containers that do not hold
        # the new block: the moved vector is no longer a member there, so a
        # pure-node search (no post-filter) would leak it under old_tau
        # (MutableEngines were tombstoned by delete() above instead)
        for key in old_nodes:
            if key in nodes:
                continue
            eng = self.store.engines[key]
            if not isinstance(eng, MutableEngine) \
                    and vid in set(int(i) for i in eng.ids):
                self.store.engines[key] = self._engine_without(eng, vid)
            self.dirty_nodes.add(key)
        if in_left or not nodes:
            self._append_leftover(b, vid, vec)
        self._sync_policy()
        # a move is visible to any role set intersecting either combination
        # (delete() above already dropped answers that contained the row);
        # old ∪ new covers both the grant and the revoke direction
        self._cache_mutated(frozenset(old_tau) | frozenset(new_tau))

    # ---------------------------------------------------------------- search
    def tombstone_pad(self, roles: Sequence[Role]) -> int:
        """How many tombstoned vectors could still surface for this role
        set: only those whose role combination at deletion time intersects
        ``roles`` — an out-of-role delete is invisible to this plan cover,
        so it must not inflate k (the former global ``len(tombstones)``
        pad over-fetched for every unrelated delete)."""
        if not self.tombstones:
            return 0
        want = set(int(r) for r in roles)
        pad = 0
        for t in self.tombstones:
            tau = self.tombstone_roles.get(t)
            if tau is None or (tau & want):
                pad += 1
        return pad

    def search(self, x: np.ndarray, role: Optional[Role] = None,
               k: Optional[int] = None, efs: int = 50,
               roles: Optional[Sequence[Role]] = None, where=None
               ) -> List[Tuple[float, int]]:
        """Authorized top-k through the unified entry point: builds a
        :class:`Query` (single- or multi-role) with tombstone-aware
        over-fetch and filters tombstoned ids from the result.  ScoreScan
        stores take the batched kernel path, exact/HNSW stores the
        per-query coordinated path — same as any static store.  ``where``
        (predicate atoms, see :class:`Query`) narrows to the attribute
        plane; filtered and unfiltered answers never share a cache entry.
        """
        k = int(k or self.k)
        if roles is None:
            assert role is not None, "search needs a role or a roles set"
            roles = (int(role),)
        else:
            roles = tuple(int(r) for r in roles)
        q = Query(vector=x, roles=roles, k=k, efs=efs, where=where)
        cache = self.result_cache
        words = self._cache_words(roles) if cache is not None else None
        pwords = None
        if cache is not None and q.where is not None:
            rf = self.store.compile_where(q.where)
            pwords = np.concatenate(rf).astype(np.uint32)
        if cache is not None:
            hit = cache.lookup(x, words, k, efs, pwords=pwords)
            if hit is not None:
                return hit
        pad = self.tombstone_pad(roles)
        res = self.store.search(
            [dataclasses.replace(q, k=k + pad)])[0]
        out = [(d, v) for d, v in res.hits
               if v not in self.tombstones][:k]
        if cache is not None:
            # stored post-tombstone-filter, so a cached answer never
            # carries a deleted id; mutations invalidate precisely
            cache.store(x, words, k, out, efs=efs, pwords=pwords)
        return out

    # --------------------------------------------------------- lazy re-optim
    def live_size(self, key) -> int:
        """Rows of node ``key``'s engine minus global and engine-local
        tombstones — the size the cost model should reason about."""
        eng = self.store.engines[key]
        dead = self.tombstones | set(getattr(eng, "tombstoned", ()))
        if not dead:
            return len(eng.ids)
        return len(set(int(i) for i in eng.ids) - dead)

    def register_base(self, key) -> None:
        """(Re-)base drift accounting for ``key`` at its current live size.

        Called at every node-creation site and after each re-optimization
        decision — the points where the node's copy/merge shape was last
        chosen; ``needs_reoptimization`` measures drift from here."""
        self._base_sizes[key] = self.live_size(key)

    def needs_reoptimization(self) -> List:
        """Nodes whose live size drifted past ``slack`` since their shape
        was last chosen — re-run copy/merge locally
        (:meth:`~repro.core.LatticeCompactor.reoptimize_node`).

        A node not yet registered (a creation site that predates drift
        accounting) is registered at its current live size on first sight,
        so its drift is measured from now on — never silently pinned to
        zero by a transient ``base == live`` fallback."""
        out = []
        for key in self.store.engines:
            live = self.live_size(key)
            base = self._base_sizes.setdefault(key, live)
            if base and abs(live - base) / base > self.slack:
                out.append(key)
        return out
