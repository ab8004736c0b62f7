"""Public wrapper for the authorized L2 top-k scan kernel.

Only the kernel launch (``kernel.l2_topk_pallas``) is jitted.  The wrapper
itself runs eagerly on every launch: host operands are cast by numpy and
reach the device through the first eager JAX operation on them (a mask
reshape, a pad), or through the kernel call when they need no pad; each
pad and cast is its own small program.  It pads queries to BQ, db to BN
and d to 128 lanes, and masks padded database rows via the in-kernel
validity predicate (all-zero auth words) and padded query rows via
all-zero role masks (+inf bounds).  The backend picks the kernel mode
(:func:`kernel_interpret`): on TPU every launch runs the compiled Mosaic
kernel, on CPU the same kernel body runs in the Pallas interpreter (the
test suite's mode), and any other backend is an error.

Auth masks are single-word (``(N,)`` + scalar/``(B,)`` role mask — role
universes up to 32 roles, the original layout) or multi-word (``(N, W)``
packed uint32 words + ``(W,)``/``(B, W)`` role masks, W = ceil(n_roles/32));
see DESIGN.md §Role Masks.  W == 1 operands take exactly the original
single-word kernel path — same block shapes, same compare — so existing
perf baselines hold.

Spans (``repro.obs``): ``l2_topk.prep`` from entry to the kernel call
(mask normalising, casts, and the issue of the host-to-device copies, pads
and casts) and ``l2_topk.dispatch`` around the kernel call and the trim of
the padded query rows (their enqueue; a compile lands here).  Copies run
asynchronously, so the host's wait for them falls where it first blocks:
in either span or in the read-back after them.  Counters on
``l2_topk.prep``: ``h2d_bytes`` (bytes of the operands that arrive as host
arrays), ``launches``, ``rows_scanned`` (N) and ``rows_padded`` (database
rows padded to ``bn`` plus query rows padded to ``bq``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from .kernel import l2_topk_pallas
from .ref import l2_topk_ref, normalize_masks, normalize_predicates


@dataclasses.dataclass(frozen=True)
class L2TopKConfig:
    bq: int = 8            # query tile rows
    bn: int = 512          # database tile rows (VMEM-resident)
    kpad: int = 128        # running top-k storage width (lane aligned)
    lane: int = 128        # feature padding multiple (MXU alignment)


def kernel_interpret() -> bool:
    """Kernel mode for the default backend: compiled on ``tpu``, the Pallas
    interpreter on ``cpu``.  Any other backend raises — a launch never falls
    back to a mode the backend was not asked for."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"l2_topk runs compiled on TPU or interpreted on CPU; the default "
        f"JAX backend is {backend!r}")


def _pad_to(x: jax.Array, m: int, axis: int, value=0):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def l2_topk(queries: jax.Array, db: jax.Array, auth_bits: jax.Array,
            role_mask, k: int, bound=None,
            config: L2TopKConfig = L2TopKConfig(),
            attr_bits=None, require=None, forbid=None
            ) -> Tuple[jax.Array, jax.Array]:
    """Authorized top-k nearest neighbours of each query under L2.

    Args:
      queries: (B, d) float32.
      db: (N, d) float32 node shard.
      auth_bits: (N,) uint32 single-word role masks, or (N, W) packed
        uint32 words for role universes wider than 32 roles.
      role_mask: querying-role mask — scalar uint32 or (B,) per query for
        single-word masks; (W,) shared or (B, W) per query for multi-word.
      k: neighbours to return (k <= config.kpad).
      bound: optional float32 coordinated-search global k-th distance;
        candidates at or beyond it are pruned in-kernel.  Scalar, or (B,)
        with one bound per query.
      attr_bits: optional (N, P) packed uint32 attribute words (predicate
        plane, DESIGN.md §Hybrid Filtered Search).  None disables the plane
        and takes the exact pre-predicate kernel path.
      require: optional (P,) shared or (B, P) per-query required-bits rows.
      forbid: optional (P,) shared or (B, P) per-query forbidden-bits rows.

    Returns:
      (dists (B, k) float32, ids (B, k) int32); empty slots are +inf / -1.
    """
    assert k <= config.kpad, (k, config.kpad)
    with obs.span("l2_topk.prep"):
        operands, pkw = _prep(queries, db, auth_bits, role_mask, bound,
                              config, attr_bits, require, forbid)
    b = queries.shape[0]
    with obs.span("l2_topk.dispatch"):
        out_d, out_i = l2_topk_pallas(
            *operands, db.shape[0], k, kpad=config.kpad, bq=config.bq,
            bn=config.bn, interpret=kernel_interpret(), **pkw)
        # the padded query rows are trimmed on the device, also enqueued
        return out_d[:b], out_i[:b]


def _host_bytes(*operands) -> int:
    """Bytes a launch sends from host to device: every operand that is not
    a ``jax.Array`` goes to the device as 4-byte float32 or uint32
    elements."""
    return sum(4 * np.size(x) for x in operands
               if x is not None and not isinstance(x, jax.Array))


def _prep(queries, db, auth_bits, role_mask, bound, config, attr_bits,
          require, forbid):
    """The kernel's padded operands and its predicate keywords."""
    obs.count("h2d_bytes", _host_bytes(queries, db, auth_bits, role_mask,
                                       bound, attr_bits, require, forbid))
    b, d = queries.shape
    n = db.shape[0]
    obs.count("launches")
    obs.count("rows_scanned", n)
    obs.count("rows_padded", (-n) % config.bn + (-b) % config.bq)
    if bound is None:
        bound = np.float32(np.inf)
    auth, mask, w = normalize_masks(auth_bits, role_mask)
    pred = normalize_predicates(attr_bits, require, forbid)
    qp = _pad_to(queries.astype(jnp.float32), config.bq, 0)
    qp = _pad_to(qp, config.lane, 1)
    # padded query rows carry all-zero role masks (nothing authorized) and
    # bound +inf
    rp = _pad_to(jnp.broadcast_to(mask, (b, w)), config.bq, 0)
    bp = _pad_to(jnp.broadcast_to(
        jnp.asarray(bound, jnp.float32).reshape(-1), (b,))[:, None],
        config.bq, 0, value=jnp.inf)
    dbp = _pad_to(db.astype(jnp.float32), config.bn, 0)
    dbp = _pad_to(dbp, config.lane, 1)
    # padded db rows carry all-zero auth words; word-major (W, N) layout so
    # each word is a contiguous lane row for the kernel's auth tile
    ap = _pad_to(auth.T, config.bn, 1)
    pkw = {}
    if pred is not None:
        attr, req, forb, p = pred
        # padded db rows carry all-zero attr words — they fail any nonzero
        # require row, and their zero auth words exclude them regardless;
        # padded query rows get all-zero require/forbid (pass-through, their
        # zero role masks already return nothing)
        pkw = dict(
            attr_words=_pad_to(attr.T, config.bn, 1),
            require=_pad_to(jnp.broadcast_to(req, (b, p)), config.bq, 0),
            forbid=_pad_to(jnp.broadcast_to(forb, (b, p)), config.bq, 0))
    return (qp, dbp, ap, rp, bp), pkw


def l2_topk_oracle(queries, db, auth_bits, role_mask, k, bound=None,
                   attr_bits=None, require=None, forbid=None):
    bound = jnp.inf if bound is None else bound
    return l2_topk_ref(queries, db, auth_bits, role_mask,
                       jnp.asarray(bound, jnp.float32), k,
                       attr_bits=attr_bits, require=require, forbid=forbid)
