"""Pure-jnp oracle for the authorized L2 top-k scan kernel.

Semantics (shared with the Pallas kernel):
  * distance = ||q - v||^2 over the database,
  * a vector is a candidate iff its auth mask intersects the query's role
    mask in ANY packed word AND its distance is strictly below ``bound``
    (the coordinated-search global k-th distance; +inf disables the bound),
  * non-candidates get distance +inf and id -1,
  * ties broken toward the smaller database id (deterministic).

Auth masks come in two layouts (DESIGN.md §Role Masks):
  * single word (role universes up to 32 roles): ``auth_bits`` is ``(N,)``
    and ``role_mask`` a scalar or ``(B,)`` vector — the original layout,
  * multi-word (W = ceil(n_roles/32) packed uint32 words): ``auth_bits`` is
    ``(N, W)`` and ``role_mask`` ``(W,)`` (shared by every query) or
    ``(B, W)`` (one word row per query).

``bound`` may be a scalar or ``(B,)`` — the batched execution engine
(DESIGN.md §Batched Execution) threads per-query coordinated-search bounds
and per-query role masks through a single kernel launch.

Predicate plane (DESIGN.md §Hybrid Filtered Search): vectors may carry
``(N, P)`` packed uint32 attribute words and queries ``(P,)`` / ``(B, P)``
require/forbid word rows.  A vector passes iff, in every word,
``(attr & require) == require`` and ``(attr & forbid) == 0`` — evaluated as
a conjunction beside the auth check.  ``attr_bits=None`` is the exact
pre-predicate code path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INF = float("inf")          # a python float: a jnp constant would allocate on
                            # the default device as this module is imported


def _per_query(x, dtype) -> jax.Array:
    """Normalize a scalar or (B,) operand to a broadcastable (·, 1) column."""
    x = jnp.asarray(x, dtype).reshape(-1)          # () -> (1,), (B,) -> (B,)
    return x[:, None]                              # broadcasts over (B, N)


def normalize_masks(auth_bits, role_mask):
    """Common (N, W) auth / (·, W) role-mask normalization for ref + ops.

    Returns ``(auth (N, W) uint32, mask (B'|1, W) uint32, W)``.  Single-word
    operands keep their legacy forms: ``(N,)`` auth with a scalar or ``(B,)``
    mask.  For ``W > 1`` the mask must carry all W words — ``(W,)`` shared or
    ``(B, W)`` per query; a bare scalar/(B,) would silently drop roles >= 32,
    so it is rejected.
    """
    auth = jnp.asarray(auth_bits, jnp.uint32)
    if auth.ndim == 1:
        auth = auth[:, None]                                     # (N, 1)
    w = auth.shape[1]
    mask = jnp.asarray(role_mask, jnp.uint32)
    if w == 1:
        mask = mask.reshape(-1)[:, None]                         # (B'|1, 1)
    elif mask.ndim == 1:
        if mask.shape[0] != w:
            raise ValueError(
                f"role_mask must carry all {w} mask words: got shape "
                f"{mask.shape} (per-query masks are (B, {w}))")
        mask = mask[None, :]                                     # (1, W)
    elif mask.ndim == 2 and mask.shape[1] == w:
        pass                                                     # (B, W)
    else:
        raise ValueError(
            f"role_mask shape {mask.shape} incompatible with {w}-word "
            f"auth masks")
    return auth, mask, w


def normalize_predicates(attr_bits, require, forbid):
    """Common (N, P) attr / (·, P) require/forbid normalization for ref + ops.

    Returns ``(attr (N, P), require (B'|1, P), forbid (B'|1, P), P)`` as
    uint32, or ``None`` when ``attr_bits`` is None (the unfiltered path).
    ``require``/``forbid`` may be ``None`` (all-zero: no constraint on that
    side), ``(P,)`` shared, or ``(B, P)`` per query — like role masks, a row
    that drops words would silently pass bits past word 0, so short rows are
    rejected.
    """
    if attr_bits is None:
        if require is not None or forbid is not None:
            raise ValueError(
                "require/forbid word rows need (N, P) attr_bits to filter on")
        return None
    attr = jnp.asarray(attr_bits, jnp.uint32)
    if attr.ndim == 1:
        attr = attr[:, None]                                     # (N, 1)
    p = attr.shape[1]

    def _rows(x, name):
        if x is None:
            return jnp.zeros((1, p), jnp.uint32)
        x = jnp.asarray(x, jnp.uint32)
        if x.ndim == 0:
            x = x.reshape(1)
        if x.ndim == 1:
            if x.shape[0] != p:
                raise ValueError(
                    f"{name} must carry all {p} predicate words: got shape "
                    f"{x.shape} (per-query rows are (B, {p}))")
            return x[None, :]                                    # (1, P)
        if x.ndim == 2 and x.shape[1] == p:
            return x                                             # (B, P)
        raise ValueError(
            f"{name} shape {x.shape} incompatible with {p}-word attr plane")

    return attr, _rows(require, "require"), _rows(forbid, "forbid"), p


def l2_topk_ref(queries: jax.Array, db: jax.Array, auth_bits: jax.Array,
                role_mask: jax.Array, bound: jax.Array, k: int,
                attr_bits=None, require=None, forbid=None):
    """Reference top-k.

    Args:
      queries: (B, d) float32.
      db: (N, d) float32.
      auth_bits: (N,) uint32 single-word masks, or (N, W) packed words.
      role_mask: querying-role mask — scalar or (B,) single-word, or
        (W,) / (B, W) word rows (see module docstring).
      bound: float32 global k-th distance bound (inf = no bound) — scalar or
        (B,) per query.
      k: number of neighbours.
      attr_bits: optional (N, P) packed uint32 attribute words.
      require: optional (P,) / (B, P) required-bits word rows.
      forbid: optional (P,) / (B, P) forbidden-bits word rows.

    Returns:
      dists (B, k) float32 (+inf for empty slots), ids (B, k) int32 (-1).
    """
    queries = queries.astype(jnp.float32)
    db = db.astype(jnp.float32)
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)      # (B, 1)
    dn = jnp.sum(db * db, axis=1)[None, :]                      # (1, N)
    dist = qn + dn - 2.0 * queries @ db.T                       # (B, N)
    auth, mask, _ = normalize_masks(auth_bits, role_mask)
    # (B', N, W) word intersections -> any-word OR; W == 1 reduces to the
    # original single-word (auth & mask) != 0 compare
    ok = ((auth[None, :, :] & mask[:, None, :]) != 0).any(axis=-1)
    dist = jnp.where(ok, dist, INF)
    pred = normalize_predicates(attr_bits, require, forbid)
    if pred is not None:
        attr, req, forb, _ = pred
        # (B', N, P) word compares -> all-word AND: every required bit set,
        # no forbidden bit set
        a = attr[None, :, :]
        pok = (((a & req[:, None, :]) == req[:, None, :]).all(axis=-1)
               & ((a & forb[:, None, :]) == 0).all(axis=-1))
        dist = jnp.where(pok, dist, INF)
    dist = jnp.where(dist < _per_query(bound, jnp.float32), dist, INF)
    # tie-break toward smaller id: sort by (dist, id) lexicographically
    n = db.shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    order = jnp.argsort(dist + ids[None, :] * 0.0, axis=1, stable=True)
    top = order[:, :k]
    top_d = jnp.take_along_axis(dist, top, axis=1)
    top_i = jnp.where(jnp.isinf(top_d), -1, top.astype(jnp.int32))
    return top_d, top_i
