"""ScoreScan — the TPU-native retrieval engine (DESIGN.md §3).

Each lattice node's vectors are packed densely; queries are scored by the
Pallas ``l2_topk`` kernel (MXU-tiled distances + in-kernel authorization
bitmask + coordinated-search bound).  Node-level pruning replaces HNSW's
beam bound: every node stores its centroid ``c`` and radius ``rho``; for a
query ``q`` the triangle inequality gives ``dist(q, v) >= (|q-c| - rho)^2``
for all members, so a node whose lower bound exceeds the global k-th
distance is skipped without touching HBM.

A node's kernel operands live on the device: the first launch sends its
centered rows, auth words and attribute words once, padded and laid out
for the kernel (``kernels/l2_topk/ops.py::prepare_node``, span
``scan.upload``), and every later launch sends only its queries, role
masks and bounds.

The backend picks the kernel mode: compiled on TPU, interpreted on CPU
(see ``kernels/l2_topk/ops.py``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from .. import obs
from ..kernels.l2_topk import (L2TopKConfig, NodeOperands, l2_topk,
                               prepare_node)


@dataclasses.dataclass
class ScoreScanIndex:
    """Engine-compatible dense scan index over one lattice node.

    ``auth_bits`` is the per-vector in-kernel authorization mask: ``(n,)``
    uint32 for role universes up to 32 roles (the single-word fast path) or
    ``(n, W)`` packed uint32 words for wider universes (W = ceil(n_roles/32),
    DESIGN.md §Role Masks).  Role-mask operands to the search methods carry
    the matching width: a scalar / ``(B,)`` for single-word indexes, a
    ``(W,)`` / ``(B, W)`` word array otherwise.

    The host arrays are the index's record.  The kernel reads a device copy
    of them in its own layout (:meth:`operands`), built at the first launch
    and kept until :meth:`set_auth_words` changes a row's words.  Write
    auth words through that method: an in-place write to ``auth_bits``
    would leave the device copy stale.
    """

    data: np.ndarray                 # (n, d) float32
    ids: np.ndarray                  # (n,) int64 external ids
    auth_bits: np.ndarray            # (n,) or (n, W) uint32 role mask words
    config: L2TopKConfig = dataclasses.field(default_factory=L2TopKConfig)
    attr_bits: Optional[np.ndarray] = None   # (n, P) uint32 predicate words
    _operands: Optional[NodeOperands] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def __post_init__(self):
        self.data = np.ascontiguousarray(self.data, dtype=np.float32)
        self.auth_bits = np.ascontiguousarray(self.auth_bits,
                                              dtype=np.uint32)
        if self.attr_bits is not None:
            self.attr_bits = np.ascontiguousarray(self.attr_bits,
                                                  dtype=np.uint32)
            if self.attr_bits.ndim == 1:
                self.attr_bits = self.attr_bits[:, None]
        self.centroid = self.data.mean(axis=0) if len(self.data) else None
        if self.centroid is not None:
            d = self.data - self.centroid
            self.radius = float(np.sqrt((d * d).sum(axis=1).max()))
            # store node-centered vectors: the ||q||^2+||v||^2-2qv norm trick
            # cancels catastrophically when magnitudes dwarf distances;
            # distances are translation-invariant, so centering at the node
            # centroid keeps the kernel's f32 math well-conditioned.
            self._centered = np.ascontiguousarray(d, dtype=np.float32)
        else:
            self.radius = 0.0
            self._centered = self.data

    def __len__(self) -> int:
        return len(self.data)

    @property
    def mask_width(self) -> int:
        """Auth-mask width in packed uint32 words (1 = single-word path)."""
        return 1 if self.auth_bits.ndim == 1 else self.auth_bits.shape[1]

    def _full_mask(self):
        """Role mask admitting every vector (engine-interface parity)."""
        if self.mask_width == 1:
            return np.uint32(0xFFFFFFFF)
        return np.full(self.mask_width, 0xFFFFFFFF, np.uint32)

    # ---------------------------------------------------------------- bounds
    def lower_bound(self, q: np.ndarray) -> float:
        """min possible squared distance from q to any member (triangle)."""
        if self.centroid is None:
            return float("inf")
        dc = float(np.linalg.norm(q - self.centroid))
        return max(0.0, dc - self.radius) ** 2

    def lower_bounds(self, qs: np.ndarray) -> np.ndarray:
        """Vectorized :meth:`lower_bound` over a (B, d) query batch."""
        if self.centroid is None:
            return np.full(len(qs), np.inf, dtype=np.float32)
        dc = np.linalg.norm(qs - self.centroid, axis=1)
        return np.maximum(0.0, dc - self.radius) ** 2

    # ------------------------------------------------------- device operands
    def operands(self) -> NodeOperands:
        """This node's kernel operands on the device, sent on first use."""
        if self._operands is None:
            self._operands = prepare_node(self._centered, self.auth_bits,
                                          self.attr_bits, self.config)
        return self._operands

    def set_auth_words(self, vid: int, words) -> None:
        """Rewrite the auth words of external id ``vid``'s row in place (a
        grant or revoke that keeps the row in this node).  The device
        operands are dropped, and sent again at the next launch."""
        self.auth_bits[self.ids == np.int64(vid)] = words
        self._operands = None

    # ---------------------------------------------------------------- search
    def _pred_kwargs(self, require, forbid):
        """Kernel predicate rows for a require/forbid pair; empty when no
        predicate is active (the exact P=0 kernel path)."""
        if require is None and forbid is None:
            return {}
        if self.attr_bits is None:
            raise ValueError(
                "predicate filter on an index with no attr_bits plane")
        return dict(require=None if require is None
                    else np.asarray(require, np.uint32),
                    forbid=None if forbid is None
                    else np.asarray(forbid, np.uint32))

    def search_masked(self, q: np.ndarray, k: int, role_mask,
                      bound: Optional[float] = None,
                      require=None, forbid=None
                      ) -> List[Tuple[float, int]]:
        """Exact authorized top-k via the Pallas kernel; ids are external.

        ``role_mask`` is a uint32 scalar (single-word indexes) or a ``(W,)``
        word array matching :attr:`mask_width`.  ``require``/``forbid`` are
        optional ``(P,)`` predicate word rows evaluated in the same launch.
        """
        if not len(self.data):
            return []
        pkw = self._pred_kwargs(require, forbid)
        node = self.operands()
        qc = (q - self.centroid).astype(np.float32)
        d, i = l2_topk(qc[None, :], node, None,
                       np.asarray(role_mask, np.uint32), k, bound=bound,
                       config=self.config, **pkw)
        d = np.asarray(d)[0]
        i = np.asarray(i)[0]
        keep = i >= 0
        return [(float(dd), int(self.ids[ii]))
                for dd, ii in zip(d[keep], i[keep])]

    def search_masked_batch(self, qs: np.ndarray, k: int,
                            role_masks: np.ndarray,
                            bounds: Optional[np.ndarray] = None,
                            require=None, forbid=None
                            ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched :meth:`search_masked`: one kernel launch for B queries.

        Args:
          qs: (B, d) float32 query batch.
          role_masks: (B,) uint32 per-query role bitmask, or (B, W) packed
            word rows for multi-word indexes (:attr:`mask_width`).
          bounds: optional (B,) float32 per-query coordinated-search bound.
          require: optional (B, P) per-query required-predicate word rows.
          forbid: optional (B, P) per-query forbidden-predicate word rows.

        Returns:
          (dists (B, k) float32, external ids (B, k) int64); empty slots are
          +inf / -1.  No Python per-query loop — the per-query bound, role,
          and predicate rows are threaded straight into the kernel wrapper.
        """
        b = len(qs)
        if not len(self.data):
            return (np.full((b, k), np.inf, np.float32),
                    np.full((b, k), -1, np.int64))
        pkw = self._pred_kwargs(require, forbid)
        node = self.operands()
        with obs.span("scan.launch", n=len(self.data), b=b,
                      w=self.mask_width, p=node.p if pkw else 0, k=k):
            qc = (np.asarray(qs, np.float32) - self.centroid).astype(
                np.float32)
            d, i = l2_topk(qc, node, None,
                           np.asarray(role_masks, np.uint32), k,
                           bound=None if bounds is None
                           else np.asarray(bounds, np.float32),
                           config=self.config, **pkw)
            return read_back(d, i, self.ids)

    def purged(self, drop) -> "ScoreScanIndex":
        """Copy of this index with the rows whose external id is in ``drop``
        physically removed (compaction's tombstone purge); auth words follow
        their rows."""
        drop = set(int(v) for v in drop)
        keep = np.fromiter((int(v) not in drop for v in self.ids),
                           bool, len(self.ids))
        return ScoreScanIndex(self.data[keep], ids=self.ids[keep],
                              auth_bits=self.auth_bits[keep],
                              config=self.config,
                              attr_bits=None if self.attr_bits is None
                              else self.attr_bits[keep])

    # engine-interface parity (used when plugged into the generic store)
    def search(self, q: np.ndarray, k: int, efs: int = 0):
        return self.search_masked(q, k, role_mask=self._full_mask())

    def begin_search(self, q: np.ndarray, efs: int):
        res = self.search_masked(q, max(efs, 1), role_mask=self._full_mask())
        internal = {int(e): j for j, e in enumerate(self.ids)}
        out = [(dd, internal[vid]) for dd, vid in res]
        return out, ("scorescan", out)

    def resume_search(self, q: np.ndarray, state, efs: int):
        res = self.search_masked(q, max(efs, 1), role_mask=self._full_mask())
        internal = {int(e): j for j, e in enumerate(self.ids)}
        return [(dd, internal[vid]) for dd, vid in res]


def read_back(d, i, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """A launch's ``(dists, ids)`` on the host, row ids made external: the
    host waits here for the kernel and the copy back (span
    ``scan.readback``)."""
    with obs.span("scan.readback"):
        # np.array (not asarray): jax buffers are read-only and callers
        # post-filter these in place
        d = np.array(d)
        i = np.asarray(i)
        return d, np.where(i >= 0, ids[np.maximum(i, 0)], np.int64(-1))


def policy_auth_words(policy) -> np.ndarray:
    """Per-vector in-kernel auth mask for a policy: ``(n,)`` uint32 when the
    role universe fits one word (the kernel's single-word fast path), else
    ``(n, W)`` packed words (DESIGN.md §Role Masks).  Exact at any width —
    no role aliasing."""
    words = policy.role_words()                       # (n, W) uint32, exact
    return words[:, 0] if words.shape[1] == 1 else words


def pack_leftover_shard(leftover_vectors, leftover_ids, policy,
                        config: Optional[L2TopKConfig] = None,
                        attr_words: Optional[np.ndarray] = None
                        ) -> Optional[ScoreScanIndex]:
    """Concatenate every leftover block into one auth-masked ScoreScan shard.

    Leftover blocks are individually tiny (below the lam scan threshold), so
    per-block scanning costs one pass — and, in the batched engine, one
    merge — per (block, micro-batch).  Packing them into a single
    :class:`ScoreScanIndex` whose per-vector ``auth_bits`` carry each block's
    role combination lets a whole micro-batch's leftover phase ride **one**
    ``l2_topk`` launch: each query row filters by its own role mask in-kernel
    (DESIGN.md §Continuous Batching).

    Returns ``None`` when there are no leftover vectors.  Role universes of
    any width pack exactly: the shard's auth masks are multi-word past 32
    roles (``W = ceil(n_roles/32)`` packed words), so the former
    ``n_roles <= 32`` refusal is gone.
    """
    blocks = [b for b in sorted(leftover_ids) if len(leftover_ids[b])]
    if not blocks:
        return None
    data = np.concatenate([leftover_vectors[b] for b in blocks])
    ids = np.concatenate([leftover_ids[b] for b in blocks])
    bits = policy_auth_words(policy)
    return ScoreScanIndex(data=data, ids=ids, auth_bits=bits[ids],
                          config=config or L2TopKConfig(),
                          attr_bits=None if attr_words is None
                          else np.asarray(attr_words, np.uint32)[ids])


def scorescan_factory(policy, config: Optional[L2TopKConfig] = None,
                      attr_words: Optional[np.ndarray] = None):
    """Engine factory wiring the per-vector auth mask words from the
    policy (single-word up to 32 roles, multi-word beyond) and, when the
    store carries a predicate plane, the (N, P) attribute words."""
    bits = policy_auth_words(policy)
    attrs = None if attr_words is None else np.asarray(attr_words, np.uint32)
    cfg = config or L2TopKConfig()

    def make(data: np.ndarray, ids: np.ndarray) -> ScoreScanIndex:
        return ScoreScanIndex(data=data, ids=ids,
                              auth_bits=bits[ids], config=cfg,
                              attr_bits=None if attrs is None else attrs[ids])
    return make


def coordinated_scan_search(store, q: np.ndarray, role: int, k: int,
                            stats=None) -> List[Tuple[float, int]]:
    """Coordinated search specialised for ScoreScan engines.

    Pure nodes first (tightens the global k-th bound), then impure / distant
    nodes in ascending lower-bound order; a node is skipped entirely when
    its centroid-radius lower bound exceeds the current global bound — the
    TPU analogue of the paper's phase-2 skip (DESIGN.md §3).
    """
    import heapq
    from ..core.coordinated import SearchStats, _TopK, _scan_leftovers

    stats = stats if stats is not None else SearchStats()
    q = np.asarray(q, dtype=np.float32)
    plan = store.plans[role]
    mask = store.authorized_mask(role)
    role_mask = store.kernel_role_mask((role,))
    rs = _TopK(k)
    _scan_leftovers(store, plan, q, rs, stats)
    pure, impure = [], []
    for key in plan.nodes:
        eng = store.engines.get(key)
        if eng is None:
            continue
        (pure if store.is_pure(key, mask) else impure).append((key, eng))
    stats.indices_visited += len(pure) + len(impure)
    for key, eng in sorted(pure, key=lambda t: t[1].lower_bound(q)):
        stats.data_touched += len(eng)
        stats.data_authorized_touched += len(eng)
        if eng.lower_bound(q) > rs.kth_dist():
            stats.phase2_skipped += 1
            stats.impure_visits += 1   # counted as a bound-skip opportunity
            continue
        for dd, vid in eng.search_masked(q, k, role_mask,
                                         bound=rs.kth_dist()):
            rs.push(dd, vid)
    for key, eng in sorted(impure, key=lambda t: t[1].lower_bound(q)):
        total, auth = store.node_total_and_auth(key, mask)
        stats.impure_visits += 1
        stats.data_touched += total
        stats.data_authorized_touched += auth
        if eng.lower_bound(q) > rs.kth_dist():
            stats.phase2_skipped += 1
            continue
        for dd, vid in eng.search_masked(q, k, role_mask,
                                         bound=rs.kth_dist()):
            if mask[vid]:
                rs.push(dd, vid)
    return rs.items()
