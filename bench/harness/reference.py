"""The plain reference and the comparison that decides ``correct``.

The reference is a brute force over the raw corpus, the raw access
assignment and, for a filtered query, the rows' raw attributes: a query
may read the rows its roles allow that satisfy its ``where`` clause.  It
imports nothing of the program and takes nothing the program made.  It works in blocks of rows: a float32 pass over every row
proposes, for each sampled query, the candidates that can be in its
authorized top-k within a safe margin, and float64 arithmetic on those
candidates decides the answer.

``compare`` holds each served answer to it and returns the numbers that
are compared, each with its limit:

- ``missing``: sampled requests that were never answered, failed, or were
  refused (limit 0);
- ``unauthorized``: hits the query may not read: outside its roles' rows
  or failing its clause (limit 0);
- ``malformed``: answers with another number of hits than min(k, rows the
  query may read), a repeated id, or distances out of order (limit 0);
- ``dist_err``: the widest gap between a served distance and the exact
  float64 distance of the served id (limit from the configuration);
- ``rank_gap``: the widest amount by which the exact distance of the j-th
  served hit exceeds the exact j-th distance of the true answer (limit
  from the configuration).  A near-tie swap reads as its tiny gap; a lost
  neighbour, a wrong node or an answer meant for another query reads as a
  large one.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

BLOCK_ELEMENTS = 1 << 24      # float32 elements per block of corpus rows
KEEP_EXTRA = 64               # candidates kept per query beyond k
# Prefilter margin on squared distances: a float32 |q|^2+|v|^2-2q.v over
# d <= 1024 terms of norms below 1e5 errs by far less than 1 unit.
MARGIN_ABS = 1.0
MARGIN_REL = 1e-3


@dataclasses.dataclass
class Answer:
    """A served answer as the comparison sees it: ids and distances, or
    ``None`` where the request got no answer."""

    ids: Optional[np.ndarray]
    dists: Optional[np.ndarray]


class Reference:
    """Exact authorized (and, for a filtered query, eligible) top-k over
    the raw data."""

    def __init__(self, vectors: np.ndarray,
                 allowed: Callable[[Tuple[int, ...]], np.ndarray],
                 eligible: Optional[Callable[[Tuple], np.ndarray]] = None):
        self.vectors = vectors
        self._allowed = allowed
        self._eligible = eligible
        self._masks: Dict = {}

    def mask(self, roles, where=None) -> np.ndarray:
        """(N,) bool: the rows ``roles`` allow that satisfy ``where``."""
        key = tuple(roles)
        if key not in self._masks:
            self._masks[key] = self._allowed(key)
        if not where:
            return self._masks[key]
        if (key, where) not in self._masks:
            self._masks[key, where] = self._masks[key] & \
                self._eligible(where)
        return self._masks[key, where]

    def exact(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """float64 squared L2 distances from ``q`` to rows ``ids``."""
        diff = self.vectors[ids].astype(np.float64) - q.astype(np.float64)
        return np.einsum("nd,nd->n", diff, diff)

    def topk(self, queries: Sequence, k_of: Sequence[int]
             ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """True (ids, float64 distances) for each query, sorted by
        (distance, id); ``queries`` carry ``vector``, ``roles`` and
        ``where``."""
        n, dim = self.vectors.shape
        qs = np.stack([q.vector for q in queries]).astype(np.float32)
        qn = np.einsum("bd,bd->b", qs, qs)
        keep = [max(1, k) + KEEP_EXTRA for k in k_of]
        cand_d = [np.empty(0, np.float32) for _ in queries]
        cand_i = [np.empty(0, np.int64) for _ in queries]
        masks = [self.mask(q.roles, q.where) for q in queries]
        rows = max(1024, BLOCK_ELEMENTS // dim)
        for lo in range(0, n, rows):
            v = self.vectors[lo:lo + rows]
            vn = np.einsum("nd,nd->n", v, v)
            d = qn[:, None] + vn[None, :] - 2.0 * (qs @ v.T)
            for j in range(len(queries)):
                ok = np.flatnonzero(masks[j][lo:lo + rows])
                if not len(ok):
                    continue
                dj = np.concatenate([cand_d[j], d[j, ok]])
                ij = np.concatenate([cand_i[j], ok + lo])
                if len(dj) > keep[j]:
                    part = np.argpartition(dj, keep[j] - 1)[:keep[j]]
                    dj, ij = dj[part], ij[part]
                cand_d[j], cand_i[j] = dj, ij
        out = []
        for j, q in enumerate(queries):
            out.append(self._settle(q, k_of[j], masks[j], cand_d[j],
                                    cand_i[j], keep[j]))
        return out

    def _settle(self, q, k, mask, d32, i32, keep):
        """float64 decision among the float32 candidates; where the kept
        set cannot be shown to hold the true top-k, every allowed row."""
        want = min(k, len(d32))
        if not want:
            return np.empty(0, np.int64), np.empty(0, np.float64)
        order = np.argsort(d32, kind="stable")
        kth = float(d32[order[want - 1]])
        margin = MARGIN_ABS + MARGIN_REL * abs(kth)
        full = len(d32) == keep and float(d32[order[-1]]) <= kth + 2 * margin
        ids = np.flatnonzero(mask) if full else \
            i32[d32 <= kth + 2 * margin]
        d64 = self.exact(q.vector, ids)
        top = np.lexsort((ids, d64))[:want]
        return ids[top], d64[top]


def compare(ref: Reference, queries: Sequence, answers: Sequence[Answer],
            limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """The numbers compared for ``answers`` to ``queries``, each beside its
    limit, as ``{name: {"value": v, "limit": l}}``."""
    missing = unauthorized = malformed = 0
    dist_err = rank_gap = 0.0
    truth = ref.topk(queries, [q.k for q in queries]) if queries else []
    for q, a, (t_ids, t_d) in zip(queries, answers, truth):
        if a is None or a.ids is None:
            missing += 1
            continue
        ids = np.asarray(a.ids, np.int64)
        dists = np.asarray(a.dists, np.float64)
        mask = ref.mask(q.roles, q.where)
        inside = (ids >= 0) & (ids < len(mask))
        if not inside.all() or not mask[ids].all():
            unauthorized += 1
            continue
        if (len(ids) != len(t_ids) or len(set(ids.tolist())) != len(ids)
                or (np.diff(dists) < 0).any()):
            malformed += 1
            continue
        if not len(ids):
            continue
        exact = ref.exact(q.vector, ids)
        dist_err = max(dist_err, float(np.abs(dists - exact).max()))
        rank_gap = max(rank_gap, float((exact - t_d).max()))
    numbers = {"missing": missing, "unauthorized": unauthorized,
               "malformed": malformed, "dist_err": dist_err,
               "rank_gap": rank_gap}
    return {name: {"value": value, "limit": float(limits.get(name, 0.0))}
            for name, value in numbers.items()}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
