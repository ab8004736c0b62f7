"""Public wrapper for the authorized L2 top-k scan kernel.

Only the kernel launch (``kernel.l2_topk_pallas``) is jitted.  The wrapper
pads queries to BQ, db to BN and d to 128 lanes, and masks padded database
rows via the in-kernel validity predicate (all-zero auth words) and padded
query rows via all-zero role masks (+inf bounds).  The backend picks the
kernel mode (:func:`kernel_interpret`): on TPU every launch runs the
compiled Mosaic kernel, on CPU the same kernel body runs in the Pallas
interpreter (the test suite's mode), and any other backend is an error.

A node's operands (its rows, auth words and attribute words) come in one
of two forms, told apart by the type of ``db``:

* a :class:`NodeOperands` bundle from :func:`prepare_node`: the operands
  already on the device in the kernel's layout (float32 rows padded to
  ``bn`` rows and ``lane`` columns, word-major auth and attribute words).
  Built once per node; a launch then pads and casts only the query side
  (queries, role masks, bounds, require/forbid rows).
* host or device arrays, laid out eagerly on every launch: host operands
  are cast by numpy and reach the device through the first eager JAX
  operation on them (a transpose, a pad), or through the kernel call when
  they need no pad; each pad, cast and transpose is its own small program.

Both forms give the kernel the same values, so results are bit-identical.
The query side is padded and cast in both by one jitted program per batch
shape (``_query_operands``): a launch issues it and the kernel call, not a
dozen small eager operations.

Auth masks are single-word (``(N,)`` + scalar/``(B,)`` role mask — role
universes up to 32 roles, the original layout) or multi-word (``(N, W)``
packed uint32 words + ``(W,)``/``(B, W)`` role masks, W = ceil(n_roles/32));
see DESIGN.md §Role Masks.  W == 1 operands take exactly the original
single-word kernel path — same block shapes, same compare — so existing
perf baselines hold.

Spans (``repro.obs``): ``scan.upload`` around :func:`prepare_node`, with
its counter ``upload_bytes`` (the host bytes it sends, once per node);
``l2_topk.prep`` from a launch's entry to the kernel call (mask
normalising, casts, and the issue of the host-to-device copies, pads and
casts) and ``l2_topk.dispatch`` around the kernel call and the trim of the
padded query rows (their enqueue; a compile lands here).  Copies run
asynchronously, so the host's wait for them falls where it first blocks:
in either span or in the read-back after them.  Counters on
``l2_topk.prep``: ``h2d_bytes`` (bytes of the operands that arrive as host
arrays; a bundle's are never among them), ``launches``,
``resident_launches`` (launches whose node operands came as a bundle),
``rows_scanned`` (N) and ``rows_padded`` (database rows padded to ``bn``
plus query rows padded to ``bq``).
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
from .ref import (as_words, l2_topk_ref, normalize_role_mask,
                  predicate_rows)


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


@dataclasses.dataclass(frozen=True)
class NodeOperands:
    """One node's kernel operands in the kernel's layout.

    ``db`` is ``(N_pad, d_pad)`` float32, zero on padded rows and lanes;
    ``auth`` is ``(W, N_pad)`` uint32 word-major auth words and ``attr``
    ``(P, N_pad)`` attribute words or ``None``, each word a contiguous lane
    row for the kernel's tiles and all-zero on padded rows; ``n`` is the
    true row count."""

    db: jax.Array
    auth: jax.Array
    attr: Optional[jax.Array]
    n: int

    @property
    def w(self) -> int:
        """Auth words per row."""
        return self.auth.shape[0]

    @property
    def p(self) -> int:
        """Attribute words per row (0: no predicate plane)."""
        return 0 if self.attr is None else self.attr.shape[0]


def _layout(db, auth_bits, attr_bits, config: L2TopKConfig) -> NodeOperands:
    """A node's operands laid out for the kernel.  Padded db rows carry
    all-zero auth words, which exclude them, and all-zero attr words, which
    fail any nonzero require row."""
    dbp = _pad_to(db.astype(jnp.float32), config.bn, 0)
    attr = None if attr_bits is None else \
        _pad_to(as_words(attr_bits).T, config.bn, 1)
    return NodeOperands(db=_pad_to(dbp, config.lane, 1),
                        auth=_pad_to(as_words(auth_bits).T, config.bn, 1),
                        attr=attr, n=db.shape[0])


def prepare_node(db, auth_bits, attr_bits=None,
                 config: L2TopKConfig = L2TopKConfig(),
                 device=None) -> NodeOperands:
    """A node's operands, sent to the device once and laid out there for
    the kernel: the bundle :func:`l2_topk` takes in place of ``db``,
    ``auth_bits`` and ``attr_bits``.

    Args take the forms :func:`l2_topk` does, as host arrays.  ``device``
    commits the operands there, so every launch over them runs on it; None
    leaves them uncommitted on the default device.  The values are those a
    launch over the same host arrays builds, so its results are
    bit-identical.  Returns once the copies are done: the upload is set-up
    work, never waited for in a launch.
    """
    with obs.span("scan.upload"):
        obs.count("upload_bytes", _host_bytes(db, auth_bits, attr_bits))

        def put(x, dtype):
            return None if x is None else jax.device_put(
                np.asarray(x, dtype), device)

        node = _layout(put(db, np.float32), put(auth_bits, np.uint32),
                       put(attr_bits, np.uint32), config)
        jax.block_until_ready((node.db, node.auth, node.attr))
        return node


def l2_topk(queries: jax.Array, db, auth_bits, role_mask, k: int,
            bound=None, config: L2TopKConfig = L2TopKConfig(),
            attr_bits=None, require=None, forbid=None
            ) -> Tuple[jax.Array, jax.Array]:
    """Authorized top-k nearest neighbours of each query under L2.

    Args:
      queries: (B, d) float32.
      db: (N, d) float32 node shard, or the node's :class:`NodeOperands`
        from :func:`prepare_node`; with a bundle, ``auth_bits`` and
        ``attr_bits`` are None.
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
        and takes the exact pre-predicate kernel path.  A bundle's
        attribute words join the launch when ``require`` or ``forbid`` is
        given.
      require: optional (P,) shared or (B, P) per-query required-bits rows.
      forbid: optional (P,) shared or (B, P) per-query forbidden-bits rows.

    Returns:
      (dists (B, k) float32, ids (B, k) int32); empty slots are +inf / -1.
    """
    assert k <= config.kpad, (k, config.kpad)
    with obs.span("l2_topk.prep"):
        n, operands, pkw = _prep(queries, db, auth_bits, role_mask, bound,
                                 config, attr_bits, require, forbid)
    b = queries.shape[0]
    with obs.span("l2_topk.dispatch"):
        out_d, out_i = l2_topk_pallas(
            *operands, n, k, kpad=config.kpad, bq=config.bq,
            bn=config.bn, interpret=kernel_interpret(), **pkw)
        # the padded query rows are trimmed on the device, also enqueued
        return out_d[:b], out_i[:b]


def _host_bytes(*operands) -> int:
    """Bytes a launch sends from host to device: every operand that is not
    a ``jax.Array`` goes to the device as 4-byte float32 or uint32
    elements."""
    return sum(4 * np.size(x) for x in operands
               if x is not None and not isinstance(x, jax.Array))


def _host(x, dtype):
    """A host operand as a numpy array of the kernel's dtype; a
    ``jax.Array`` (or tracer) and None pass as they are."""
    if x is None or isinstance(x, jax.Array):
        return x
    return np.asarray(x, dtype)


@functools.partial(jax.jit, static_argnames=("w", "p", "bq", "lane"))
def _query_operands(queries, role_mask, bound, require, forbid, *, w: int,
                    p: int, bq: int, lane: int):
    """A launch's query-side operands, padded, as one program per batch
    shape: queries to ``bq`` rows and ``lane`` columns, ``(B, W)`` role
    words, a ``(B, 1)`` bound column and, with ``p`` words, ``(B, P)``
    require/forbid rows.  Padded query rows carry all-zero role masks
    (nothing authorized), bound +inf and all-zero require/forbid rows
    (pass-through: their zero role masks already return nothing)."""
    b = queries.shape[0]
    qp = _pad_to(_pad_to(queries.astype(jnp.float32), bq, 0), lane, 1)
    rp = _pad_to(jnp.broadcast_to(normalize_role_mask(role_mask, w), (b, w)),
                 bq, 0)
    bp = _pad_to(jnp.broadcast_to(
        jnp.asarray(bound, jnp.float32).reshape(-1), (b,))[:, None],
        bq, 0, value=jnp.inf)
    if not p:
        return qp, rp, bp, None, None
    req, forb = predicate_rows(require, forbid, p)
    return (qp, rp, bp,
            _pad_to(jnp.broadcast_to(req, (b, p)), bq, 0),
            _pad_to(jnp.broadcast_to(forb, (b, p)), bq, 0))


def _prep(queries, db, auth_bits, role_mask, bound, config, attr_bits,
          require, forbid):
    """The node's row count, the kernel's padded operands and its
    predicate keywords."""
    filtered = not (attr_bits is None and require is None
                    and forbid is None)
    resident = isinstance(db, NodeOperands)
    if resident:
        if auth_bits is not None or attr_bits is not None:
            raise ValueError(
                "a NodeOperands bundle carries the node's auth and attr "
                "words: pass auth_bits=None and attr_bits=None")
        obs.count("resident_launches")
    # nothing of a bundle crosses
    obs.count("h2d_bytes", _host_bytes(
        queries, None if resident else db, auth_bits, role_mask, bound,
        attr_bits, require, forbid))
    node = db if resident else _layout(db, auth_bits, attr_bits, config)
    b = queries.shape[0]
    obs.count("launches")
    obs.count("rows_scanned", node.n)
    obs.count("rows_padded", (-node.n) % config.bn + (-b) % config.bq)
    p = 0
    if filtered:
        if node.attr is None:
            raise ValueError(
                "require/forbid word rows need (N, P) attr_bits to filter on")
        p = node.p
        obs.count("filtered_launches")
    qp, rp, bp, req, forb = _query_operands(
        _host(queries, np.float32), _host(role_mask, np.uint32),
        _host(np.inf if bound is None else bound, np.float32),
        _host(require, np.uint32), _host(forbid, np.uint32),
        w=node.w, p=p, bq=config.bq, lane=config.lane)
    pkw = dict(attr_words=node.attr, require=req, forbid=forb) if p else {}
    return node.n, (qp, node.db, node.auth, rp, bp), pkw


def l2_topk_oracle(queries, db, auth_bits, role_mask, k, bound=None,
                   attr_bits=None, require=None, forbid=None):
    bound = jnp.inf if bound is None else bound
    return l2_topk_ref(queries, db, auth_bits, role_mask,
                       jnp.asarray(bound, jnp.float32), k,
                       attr_bits=attr_bits, require=require, forbid=forbid)
