"""Numpy HNSW (Malkov & Yashunin) with *resumable* base-layer search.

Faithful to the paper's engine (§2.1 / Appendix A):
  * geometric level assignment with mL = 1/ln(M),
  * efc-bounded layer searches during insertion, neighbor-diversity pruning,
  * M links per upper-layer node, M0 = 2M at the base layer,
  * query = greedy upper-layer descent + base-layer beam search (capacity efs).

Coordinated search (paper Algorithm 17) needs to *resume* a base-layer search
with a larger beam after comparing against the global top-k bound, without
rescanning: ``begin_search`` returns a :class:`SearchState` holding the
candidate heap + visited set, and ``resume_search`` continues from it.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class SearchState:
    """Resumable base-layer beam state (candidate heap C, result heap W)."""

    candidates: List[Tuple[float, int]]          # min-heap of (dist, id)
    results: List[Tuple[float, int]]             # max-heap [(-dist, id)]
    visited: set
    expansions: int = 0                          # nodes expanded so far

    def top_k(self, k: int) -> List[Tuple[float, int]]:
        out = sorted([(-d, i) for d, i in self.results])
        return out[:k]

    def kth_dist(self, k: int) -> float:
        out = self.top_k(k)
        return out[k - 1][0] if len(out) >= k else float("inf")


class HNSWIndex:
    """HNSW over an ``(n, d)`` float32 array of vectors with external ids.

    ``auth_bits`` optionally carries per-vector authorization mask words —
    ``(n,)`` uint32 for role universes up to 32 roles or ``(n, W)`` packed
    words beyond (same layout as the ScoreScan engine, DESIGN.md §Role
    Masks).  When present the index is a ``MaskedEngine``:
    :meth:`search_masked` filters the beam's results by word-mask
    intersection.  ``auth_bits`` is a property over the internal growth
    buffer that raises ``AttributeError`` on an auth-less index, so a
    plain HNSW index still does not satisfy the runtime-checkable
    ``MaskedEngine`` protocol; :attr:`has_auth` is the explicit
    discriminator (no ``hasattr`` probes — authlint ``hasattr-probe``).

    Row storage (``data``/``ids``/``levels``/``auth_bits``) lives in
    capacity-doubling growth buffers exposed as prefix views, so
    :meth:`insert` appends in amortized O(d) instead of the O(n·d)
    re-allocation an ``np.vstack`` per insert would cost (authlint
    ``vstack-growth``).
    """

    def __init__(self, data: np.ndarray, ids: Optional[np.ndarray] = None,
                 M: int = 16, efc: int = 100, seed: int = 0,
                 auth_bits: Optional[np.ndarray] = None,
                 attr_bits: Optional[np.ndarray] = None):
        assert data.ndim == 2
        data = np.ascontiguousarray(data, dtype=np.float32)
        ids = (np.arange(len(data), dtype=np.int64) if ids is None
               else np.asarray(ids, dtype=np.int64))
        self._n = len(data)
        cap = max(self._n, 8)
        self._data_buf = np.empty((cap, data.shape[1]), np.float32)
        self._data_buf[:self._n] = data
        self._ids_buf = np.empty(cap, np.int64)
        self._ids_buf[:self._n] = ids
        self._levels_buf = np.zeros(cap, dtype=np.int32)
        self._auth_buf: Optional[np.ndarray] = None
        if auth_bits is not None:
            auth_bits = np.ascontiguousarray(auth_bits, dtype=np.uint32)
            assert len(auth_bits) == self._n, \
                (auth_bits.shape, data.shape)
            self._auth_buf = np.empty((cap,) + auth_bits.shape[1:],
                                      np.uint32)
            self._auth_buf[:self._n] = auth_bits
        self._attr_buf: Optional[np.ndarray] = None
        if attr_bits is not None:
            attr_bits = np.ascontiguousarray(attr_bits, dtype=np.uint32)
            if attr_bits.ndim == 1:
                attr_bits = attr_bits[:, None]
            assert len(attr_bits) == self._n, \
                (attr_bits.shape, data.shape)
            self._attr_buf = np.empty((cap, attr_bits.shape[1]), np.uint32)
            self._attr_buf[:self._n] = attr_bits
        self.M = int(M)
        self.M0 = 2 * int(M)
        self.efc = int(efc)
        self.mL = 1.0 / math.log(self.M)
        self._seed = int(seed)               # kept for purge-time rebuilds
        self._rng = np.random.default_rng(seed)
        # neighbors[layer][node] -> list of internal ids
        self.neighbors: List[Dict[int, List[int]]] = []
        self.entry: int = -1
        self.max_level: int = -1
        self.tombstoned: set = set()        # external ids marked deleted
        for i in range(self._n):
            self._insert(i)

    # ------------------------------------------------------------ row storage
    @property
    def data(self) -> np.ndarray:
        return self._data_buf[:self._n]

    @property
    def ids(self) -> np.ndarray:
        return self._ids_buf[:self._n]

    @property
    def levels(self) -> np.ndarray:
        return self._levels_buf[:self._n]

    @property
    def has_auth(self) -> bool:
        """Whether this index carries per-vector auth words (and thus
        satisfies the ``MaskedEngine`` protocol)."""
        return self._auth_buf is not None

    @property
    def auth_bits(self) -> np.ndarray:
        if self._auth_buf is None:
            # raising (not returning None) keeps a plain index outside the
            # runtime-checkable MaskedEngine protocol, whose isinstance
            # check is attribute presence
            raise AttributeError(
                "auth_bits: HNSWIndex built without auth words "
                "(check .has_auth / isinstance(x, MaskedEngine))")
        return self._auth_buf[:self._n]

    @property
    def attr_bits(self) -> Optional[np.ndarray]:
        """Per-vector (n, P) predicate words, or ``None`` when the index has
        no attribute plane (same convention as ScoreScanIndex)."""
        if self._attr_buf is None:
            return None
        return self._attr_buf[:self._n]

    def _grow(self, need: int) -> None:
        cap = len(self._ids_buf)
        if need <= cap:
            return
        new_cap = max(int(need), 2 * cap)
        for name in ("_data_buf", "_ids_buf", "_levels_buf", "_auth_buf",
                     "_attr_buf"):
            buf = getattr(self, name)
            if buf is None:
                continue
            nb = np.zeros((new_cap,) + buf.shape[1:], buf.dtype)
            nb[:self._n] = buf[:self._n]
            setattr(self, name, nb)

    # ------------------------------------------------------------- distances
    def _dist(self, q: np.ndarray, idx: Sequence[int]) -> np.ndarray:
        idx = np.asarray(idx, dtype=np.int64)
        diff = self.data[idx] - q
        return np.einsum("nd,nd->n", diff, diff)

    def _dist1(self, q: np.ndarray, i: int) -> float:
        d = self.data[i] - q
        return float(d @ d)

    # -------------------------------------------------------------- building
    def _insert(self, i: int) -> None:
        level = int(-math.log(max(self._rng.random(), 1e-12)) * self.mL)
        self.levels[i] = level
        while len(self.neighbors) <= level:
            self.neighbors.append({})
        for l in range(level + 1):
            self.neighbors[l][i] = []
        if self.entry < 0:
            self.entry = i
            self.max_level = level
            return
        q = self.data[i]
        ep = self.entry
        # greedy descent above the insertion level
        for l in range(self.max_level, level, -1):
            ep = self._greedy_step(q, ep, l)
        # efc beam search + connect at each layer from min(level, max) down
        for l in range(min(level, self.max_level), -1, -1):
            w = self._search_layer(q, [ep], self.efc, l)
            mmax = self.M0 if l == 0 else self.M
            chosen = self._select_neighbors(q, [c for _, c in w], self.M)
            self.neighbors[l][i] = list(chosen)
            for c in chosen:
                nb = self.neighbors[l][c]
                nb.append(i)
                if len(nb) > mmax:
                    ds = self._dist(self.data[c], nb)
                    keep = self._select_neighbors(self.data[c], list(nb), mmax,
                                                  dists=ds)
                    self.neighbors[l][c] = list(keep)
            ep = w[0][1] if w else ep
        if level > self.max_level:
            self.max_level = level
            self.entry = i

    def _select_neighbors(self, q: np.ndarray, cand: List[int], m: int,
                          dists: Optional[np.ndarray] = None) -> List[int]:
        """Diversity-preserving heuristic (SELECT-NEIGHBORS-HEURISTIC)."""
        if dists is None:
            dists = self._dist(q, cand)
        order = np.argsort(dists)
        chosen: List[int] = []
        chosen_d: List[float] = []
        for j in order:
            c = cand[int(j)]
            if len(chosen) >= m:
                break
            dc = float(dists[int(j)])
            ok = True
            for cc in chosen:
                if self._dist1(self.data[c], cc) < dc:
                    ok = False
                    break
            if ok:
                chosen.append(c)
                chosen_d.append(dc)
        if not chosen and len(cand):
            chosen = [cand[int(order[0])]]
        return chosen

    def _greedy_step(self, q: np.ndarray, ep: int, layer: int) -> int:
        cur = ep
        cur_d = self._dist1(q, cur)
        improved = True
        while improved:
            improved = False
            nbrs = self.neighbors[layer].get(cur, [])
            if not nbrs:
                break
            ds = self._dist(q, nbrs)
            j = int(np.argmin(ds))
            if ds[j] < cur_d:
                cur, cur_d = nbrs[j], float(ds[j])
                improved = True
        return cur

    def _search_layer(self, q: np.ndarray, eps: Sequence[int], ef: int,
                      layer: int) -> List[Tuple[float, int]]:
        state = self._init_state(q, eps)
        self._expand(q, state, ef, layer, max_expansions=None)
        return sorted([(-d, i) for d, i in state.results])[:ef]

    # ------------------------------------------------------------- searching
    def _init_state(self, q: np.ndarray, eps: Sequence[int]) -> SearchState:
        ds = self._dist(q, list(eps))
        cand = [(float(d), int(e)) for d, e in zip(ds, eps)]
        heapq.heapify(cand)
        results = [(-d, i) for d, i in cand]
        heapq.heapify(results)
        return SearchState(candidates=cand, results=results,
                           visited=set(int(e) for e in eps))

    def _expand(self, q: np.ndarray, state: SearchState, ef: int, layer: int,
                max_expansions: Optional[int]) -> None:
        """Beam-expand until exhaustion/termination; W capacity = ``ef``."""
        C, W = state.candidates, state.results
        while C:
            d, v = C[0]
            worst = -W[0][0] if len(W) >= ef else float("inf")
            if d > worst and len(W) >= ef:
                break
            if max_expansions is not None and state.expansions >= max_expansions:
                break
            heapq.heappop(C)
            state.expansions += 1
            nbrs = [u for u in self.neighbors[layer].get(v, [])
                    if u not in state.visited]
            if not nbrs:
                continue
            state.visited.update(nbrs)
            ds = self._dist(q, nbrs)
            worst = -W[0][0] if len(W) >= ef else float("inf")
            for du, u in zip(ds, nbrs):
                du = float(du)
                if len(W) < ef or du < worst:
                    heapq.heappush(C, (du, u))
                    heapq.heappush(W, (-du, u))
                    if len(W) > ef:
                        heapq.heappop(W)
                    worst = -W[0][0] if len(W) >= ef else float("inf")

    def _descend(self, q: np.ndarray) -> int:
        ep = self.entry
        for l in range(self.max_level, 0, -1):
            ep = self._greedy_step(q, ep, l)
        return ep

    # ------------------------------------------------- MutableEngine (App. I)
    def insert(self, vid: int, vec: np.ndarray,
               auth_bits=None, attr_bits=None) -> None:
        """Incremental insert of one vector with external id ``vid``.

        Re-inserting an id that is already linked (a tombstoned vector being
        re-granted) only clears its tombstone mark — the graph keeps the
        original row.  For auth-carrying indexes ``auth_bits`` supplies the
        new row's mask words (scalar / ``(W,)``); callers that track
        authorization (DynamicStore) pass the row's role-combination mask.
        ``attr_bits`` likewise supplies the row's (P,) predicate words on an
        attribute-carrying index.
        """
        vid = int(vid)
        if np.any(self.ids == vid):
            self.tombstoned.discard(vid)
            # the row is kept, but its authorization may have changed (e.g.
            # a revoke-then-grant cycle): refresh the auth words so the
            # documented contract holds on this path too
            if auth_bits is not None and self.has_auth:
                self.auth_bits[self.ids == np.int64(vid)] = \
                    np.asarray(auth_bits, np.uint32)
            if attr_bits is not None and self._attr_buf is not None:
                self.attr_bits[self.ids == np.int64(vid)] = \
                    np.asarray(attr_bits, np.uint32)
            return
        row = None
        if self.has_auth:
            width = self._auth_buf.shape[1:]
            row = (np.zeros(width, np.uint32) if auth_bits is None
                   else np.asarray(auth_bits, np.uint32))
            assert row.shape == width, (row.shape, self._auth_buf.shape)
        arow = None
        if self._attr_buf is not None:
            p = self._attr_buf.shape[1]
            arow = (np.zeros(p, np.uint32) if attr_bits is None
                    else np.asarray(attr_bits, np.uint32).reshape(p))
        n = self._n
        self._grow(n + 1)
        self._data_buf[n] = np.asarray(vec, np.float32)
        self._ids_buf[n] = np.int64(vid)
        self._levels_buf[n] = 0
        if row is not None:
            self._auth_buf[n] = row
        if arow is not None:
            self._attr_buf[n] = arow
        self._n = n + 1
        self.tombstoned.discard(vid)
        self._insert(n)

    def purged(self, drop) -> "HNSWIndex":
        """Rebuild without the rows whose external id is in ``drop``
        (compaction's tombstone purge).  A graph cannot cheaply unlink rows,
        so this is a full O(n log n) rebuild with the same M/efc/seed; the
        compactor amortizes it over many deletes.  Tombstone marks for
        surviving rows (there should be none after a full purge) carry over;
        auth words follow their rows."""
        drop = set(int(v) for v in drop)
        keep = np.fromiter((int(v) not in drop for v in self.ids),
                           bool, len(self.ids))
        bits = self.auth_bits[keep] if self.has_auth else None
        attrs = None if self._attr_buf is None else self.attr_bits[keep]
        out = HNSWIndex(self.data[keep], ids=self.ids[keep], M=self.M,
                        efc=self.efc, seed=self._seed, auth_bits=bits,
                        attr_bits=attrs)
        survivors = set(int(i) for i in out.ids)
        out.tombstoned = {v for v in self.tombstoned
                          if v not in drop and v in survivors}
        return out

    # -------------------------------------------------- MaskedEngine surface
    def _mask_hits(self, internal: Sequence[int], role_mask) -> np.ndarray:
        """Word-mask intersection test for internal row indices."""
        m = np.atleast_1d(np.asarray(role_mask, np.uint32))
        rows = self.auth_bits[np.asarray(internal, np.int64)]
        if rows.ndim == 1:
            rows = rows[:, None]
        assert m.shape[0] == rows.shape[1], \
            (m.shape, self.auth_bits.shape)
        return ((rows & m[None, :]) != 0).any(axis=1)

    def _pred_hits(self, internal: Sequence[int], require, forbid
                   ) -> np.ndarray:
        """Predicate word test for internal row indices: every required bit
        set, no forbidden bit set, in every word."""
        if self._attr_buf is None:
            raise ValueError(
                "predicate filter on an index with no attr_bits plane")
        rows = self.attr_bits[np.asarray(internal, np.int64)]
        p = rows.shape[1]
        req = (np.zeros(p, np.uint32) if require is None
               else np.asarray(require, np.uint32).reshape(p))
        forb = (np.zeros(p, np.uint32) if forbid is None
                else np.asarray(forbid, np.uint32).reshape(p))
        return (((rows & req[None, :]) == req[None, :])
                & ((rows & forb[None, :]) == 0)).all(axis=1)

    def search_masked(self, q: np.ndarray, k: int, role_mask,
                      bound: Optional[float] = None, efs: Optional[int] = None,
                      require=None, forbid=None
                      ) -> List[Tuple[float, int]]:
        """Authorized top-k: beam search, then filter by the query's role
        mask words, the optional predicate require/forbid word rows, and the
        optional coordinated-search ``bound``.  The beam is approximate like
        any HNSW search; authorization and predicates are exact —
        an unauthorized or non-matching vector can never be returned."""
        assert self.has_auth, \
            "HNSWIndex built without auth_bits cannot search_masked"
        res, _ = self.begin_search(q, max(int(efs or 0), 4 * k, 64))
        if not res:
            return []
        keep = self._mask_hits([i for _, i in res], role_mask)
        if require is not None or forbid is not None:
            keep = keep & self._pred_hits([i for _, i in res],
                                          require, forbid)
        out = []
        for ok, (d, i) in zip(keep, res):
            vid = int(self.ids[i])
            if not ok or vid in self.tombstoned:
                continue
            if bound is not None and d >= bound:
                continue
            out.append((float(d), vid))
        return out[:k]

    def tombstone(self, vid: int) -> None:
        """Mark external id ``vid`` deleted: the row stays in the graph (it
        still routes the beam) but ``search`` filters it from results."""
        self.tombstoned.add(int(vid))

    def search(self, q: np.ndarray, k: int, efs: int) -> List[Tuple[float, np.int64]]:
        """Standard top-k: returns [(dist, external_id)] sorted ascending."""
        res, _ = self.begin_search(q, max(efs, k))
        out = [(d, self.ids[i]) for d, i in res
               if int(self.ids[i]) not in self.tombstoned]
        return out[:k]

    def begin_search(self, q: np.ndarray, efs: int
                     ) -> Tuple[List[Tuple[float, int]], SearchState]:
        """Phase-1 (uninflated) search; state allows resumption (Alg. 17)."""
        q = np.asarray(q, dtype=np.float32)
        if self.entry < 0:
            return [], SearchState([], [], set())
        ep = self._descend(q)
        state = self._init_state(q, [ep])
        self._expand(q, state, int(efs), 0, max_expansions=None)
        res = sorted([(-d, i) for d, i in state.results])[:efs]
        return [(d, int(i)) for d, i in res], state

    def resume_search(self, q: np.ndarray, state: SearchState, efs: int
                      ) -> List[Tuple[float, int]]:
        """Continue the base-layer beam with an inflated capacity ``efs``.

        Re-seeds the candidate heap from the current result set so expansion
        can widen beyond the previous beam's frontier, then expands under the
        larger capacity.  Returns the (unfiltered) result list.
        """
        q = np.asarray(q, dtype=np.float32)
        for negd, i in state.results:
            heapq.heappush(state.candidates, (-negd, i))
        self._expand(q, state, int(efs), 0, max_expansions=None)
        res = sorted([(-d, i) for d, i in state.results])[:efs]
        return [(d, int(i)) for d, i in res]

    def __len__(self) -> int:
        return len(self.data)
