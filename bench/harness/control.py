"""The control: the reference put in the program's place, one precision down.

The configurations state float32 vectors and float32 distances, and the
kernel pins its products to full float32.  The step that would tempt a
later change is one bfloat16 pass on the matrix unit, the TPU's default
for a float32 product: the query and corpus values rounded to bfloat16,
their products summed in float32, the squared norms kept in float32.  The
control computes exactly that, in numpy on the host, so that no compiler
can fold the rounding away, and its answers go through the same comparison
as the program's.  A limit that the control passes separates nothing.
"""
from __future__ import annotations

from typing import List, Sequence

import ml_dtypes
import numpy as np

from .reference import Answer, Reference

CONTROL_ROWS = 1 << 17


def _bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


def bf16_answers(ref: Reference, queries: Sequence) -> List[Answer]:
    """Each query's top-k by one-pass bfloat16 distances over the rows it
    may read, ties to the smaller id."""
    rounded = _bf16(ref.vectors)
    out = []
    for q in queries:
        qv = np.asarray(q.vector, np.float32)
        qn = np.float32(qv @ qv)
        qr = _bf16(qv)
        ids = np.flatnonzero(ref.mask(q.roles, q.where))
        best_d = np.empty(0, np.float32)
        best_i = np.empty(0, np.int64)
        for lo in range(0, len(ids), CONTROL_ROWS):
            part = ids[lo:lo + CONTROL_ROWS]
            v = ref.vectors[part]
            vn = np.einsum("nd,nd->n", v, v)
            d = (qn + vn - np.float32(2) * (rounded[part] @ qr)) \
                .astype(np.float32)
            best_d = np.concatenate([best_d, d])
            best_i = np.concatenate([best_i, part])
            if len(best_d) > q.k:
                top = np.argpartition(best_d, q.k - 1)[:q.k]
                best_d, best_i = best_d[top], best_i[top]
        order = np.lexsort((best_i, best_d))[:q.k]
        out.append(Answer(ids=best_i[order], dists=best_d[order]))
    return out
