"""Pallas TPU kernel: authorized L2 distance scan + running top-k.

This is the compute hot-spot of the TPU-native ScoreScan engine (DESIGN.md
§3): each lattice node's vectors are streamed HBM→VMEM in (BN, d) tiles, the
MXU computes the query-tile × db-tile distance block, authorization and the
coordinated-search bound are applied *in-kernel* — per-query (BQ, W) role
words against (W, BN) db auth words (W = ceil(n_roles/32) packed uint32
words, statically unrolled; W=1 is the original single-word compare) and a
per-query (BQ, 1) bound column, so one launch serves a batch of queries
with distinct roles and distinct bounds (DESIGN.md §Batched Execution,
§Role Masks) — and a per-query running
top-k is maintained across the sequential db-tile grid dimension in the
revisited output block (classic Pallas reduction pattern).

Top-k extraction uses only elementwise ops + row reductions (min / masked
min) — no gathers — so it lowers cleanly to the TPU vector unit:
  for t in range(k):
      m   = row-min(dist)
      sel = row-min(where(dist == m, id, INT_MAX))       # smallest id wins
      emit (m, sel); dist = where(id == sel, +inf, dist)
The same trick merges the tile's sorted k with the running sorted k.

VMEM budget per grid step (defaults BQ=8, BN=512, d=128, KPAD=128):
  q tile 8*128*4 = 4 KiB, db tile 512*128*4 = 256 KiB, dist 8*512*4 = 16 KiB,
  running top-k 2*8*128*4 = 8 KiB  → well under the ~16 MiB VMEM/core.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF = float("inf")          # python scalars: jnp constants would be captured
IMAX = 2 ** 31 - 1          # as traced kernel constants, which pallas rejects


def _extract_topk(dist, ids, k: int, kpad: int):
    """Row-wise smallest-k of (dist, ids) without gathers. Returns sorted
    (BQ, kpad) arrays (slots past k stay +inf / -1).

    Slot ``t`` is written by a lane-iota select, not an indexed update: an
    ``.at[:, t].set`` lowers to ``scatter``, which Mosaic cannot compile."""
    bq = dist.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, kpad), 1)
    out_d = jnp.full((bq, kpad), INF, dtype=jnp.float32)
    out_i = jnp.full((bq, kpad), -1, dtype=jnp.int32)
    for t in range(k):
        m = jnp.min(dist, axis=1, keepdims=True)                   # (BQ, 1)
        sel = jnp.min(jnp.where(dist == m, ids, jnp.int32(IMAX)),
                      axis=1, keepdims=True)
        alive = jnp.isfinite(m)
        slot = lane == t
        out_d = jnp.where(slot, jnp.where(alive, m, jnp.float32(INF)), out_d)
        out_i = jnp.where(slot, jnp.where(alive, sel, jnp.int32(-1)), out_i)
        dist = jnp.where(ids == sel, jnp.float32(INF), dist)
    return out_d, out_i


def _l2_topk_kernel(n_total_ref,
                    q_ref, qn_ref, role_mask_ref, bound_ref,
                    db_ref, dbn_ref, auth_ref,
                    *rest, k: int, kpad: int, bn: int,
                    n_words: int, n_pwords: int = 0):
    # predicate-plane refs ride between the auth words and the outputs when
    # present; n_pwords is static, so n_pwords == 0 traces to exactly the
    # pre-predicate kernel (same refs, same jaxpr — pinned bit-exact)
    if n_pwords:
        attr_ref, req_ref, forb_ref, out_d_ref, out_i_ref = rest
    else:
        out_d_ref, out_i_ref = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_d_ref[...] = jnp.full(out_d_ref.shape, INF, dtype=jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, -1, dtype=jnp.int32)

    q = q_ref[...]                                   # (BQ, d)
    db = db_ref[...]                                 # (BN, d)
    qn = qn_ref[...]                                 # (BQ, 1)
    dbn = dbn_ref[...]                               # (1, BN)
    # HIGHEST, not Mosaic's default: on a v5e the default f32 contraction
    # is a single bfloat16 pass, off by units on distances between vectors
    # of squared norm ~3,000, enough to swap neighbours; the full-f32
    # contraction stays within 1e-3 of float64
    dist = qn + dbn - 2.0 * jax.lax.dot_general(
        q, db, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (BQ, BN) via MXU

    bq = q.shape[0]
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    # per-query role words / bounds broadcast over the tile: auth is
    # (n_words, BN) db words, role_mask is (BQ, n_words) query words, and a
    # vector is authorized when ANY word intersects.  n_words is static, so
    # the word loop unrolls; n_words == 1 is exactly the old single-word
    # compare (one (1, BN) & (BQ, 1) broadcast).
    auth = (auth_ref[0:1, :] & role_mask_ref[:, 0:1]) != 0     # (BQ, BN)
    for w in range(1, n_words):
        auth |= (auth_ref[w:w + 1, :] & role_mask_ref[:, w:w + 1]) != 0
    valid = auth & (col < n_total_ref[0, 0]) & (dist < bound_ref[...])
    # predicate plane: attr is (n_pwords, BN) db words, require/forbid are
    # (BQ, n_pwords) query rows; a vector passes iff in EVERY word all
    # required bits are set and no forbidden bit is — all-word AND, the dual
    # of the auth plane's any-word OR.  Statically unrolled like the auth
    # loop; absent at n_pwords == 0.
    for p in range(n_pwords):
        a = attr_ref[p:p + 1, :]
        req = req_ref[:, p:p + 1]
        valid &= ((a & req) == req) & ((a & forb_ref[:, p:p + 1]) == 0)
    dist = jnp.where(valid, dist, INF)

    tile_d, tile_i = _extract_topk(dist, col, k, kpad)
    cand_d = jnp.concatenate([out_d_ref[...], tile_d], axis=1)   # (BQ, 2*kpad)
    cand_i = jnp.concatenate([out_i_ref[...], tile_i], axis=1)
    # merge: ids may be -1 (empty) — remap to IMAX for the smallest-id rule
    merge_ids = jnp.where(cand_i < 0, IMAX, cand_i)
    new_d, new_i = _extract_topk(cand_d, merge_ids, k, kpad)
    new_i = jnp.where(new_i == IMAX, -1, new_i)
    out_d_ref[...] = new_d
    out_i_ref[...] = new_i


@functools.partial(jax.jit, static_argnames=("k", "kpad", "bq", "bn",
                                             "interpret"))
def l2_topk_pallas(queries: jax.Array, db: jax.Array, auth_words: jax.Array,
                   role_mask: jax.Array, bound: jax.Array, n_total: int,
                   k: int, *, interpret: bool, kpad: int = 128, bq: int = 8,
                   bn: int = 512,
                   attr_words: jax.Array = None, require: jax.Array = None,
                   forbid: jax.Array = None) -> Tuple[jax.Array, jax.Array]:
    """Launch the kernel on padded operands (see ops.l2_topk for padding).

    ``auth_words`` is the (W, N) word-major per-vector auth mask and
    ``role_mask`` the (B, W) per-query word rows (W = 1 reproduces the
    original single-word operands bit-exactly); ``bound`` is a (B, 1)
    per-query column.  ``attr_words`` (P, N) / ``require`` / ``forbid``
    (B, P) optionally add the predicate plane — all three or none; None is
    the exact pre-predicate launch (same operand list, same traced kernel).
    All are tiled along the grid axes like the query/db norms, so a batch of
    queries with distinct roles, bounds, and predicates shares one launch.
    """
    b, d = queries.shape
    n = db.shape[0]
    w = auth_words.shape[0]
    assert b % bq == 0 and n % bn == 0, (b, n, bq, bn)
    assert auth_words.shape == (w, n)
    assert role_mask.shape == (b, w) and bound.shape == (b, 1)
    p = 0 if attr_words is None else attr_words.shape[0]
    if p:
        assert attr_words.shape == (p, n)
        assert require.shape == (b, p) and forbid.shape == (b, p)
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)       # (B, 1)
    dbn = jnp.sum(db * db, axis=1)[None, :]                      # (1, N)
    n_total2 = jnp.asarray(n_total, jnp.int32).reshape(1, 1)
    grid = (b // bq, n // bn)
    kernel = functools.partial(_l2_topk_kernel, k=k, kpad=kpad, bn=bn,
                               n_words=w, n_pwords=p)
    in_specs = [
        pl.BlockSpec((1, 1), lambda i, j: (0, 0)),           # n_total
        pl.BlockSpec((bq, d), lambda i, j: (i, 0)),          # queries
        pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),          # |q|^2
        pl.BlockSpec((bq, w), lambda i, j: (i, 0)),          # role words
        pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),          # bounds
        pl.BlockSpec((bn, d), lambda i, j: (j, 0)),          # db tile
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),          # |v|^2 tile
        pl.BlockSpec((w, bn), lambda i, j: (0, j)),          # auth words
    ]
    operands = [n_total2, queries, qn, role_mask, bound, db, dbn, auth_words]
    if p:
        in_specs += [
            pl.BlockSpec((p, bn), lambda i, j: (0, j)),      # attr words
            pl.BlockSpec((bq, p), lambda i, j: (i, 0)),      # require rows
            pl.BlockSpec((bq, p), lambda i, j: (i, 0)),      # forbid rows
        ]
        operands += [attr_words, require, forbid]
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, kpad), lambda i, j: (i, 0)),       # revisited
            pl.BlockSpec((bq, kpad), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kpad), jnp.float32),
            jax.ShapeDtypeStruct((b, kpad), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return out_d[:, :k], out_i[:, :k]
