"""jaxpr-level audit of the authorized top-k kernel wrapper.

The AST rules prove host-side mask discipline; this module audits the
*traced computation*: a refactor of ``l2_topk`` that stops threading the
auth-word / role-mask operands into the compiled kernel would pass every
host-side rule while silently returning unauthorized neighbours.  Two
checks, both cheap enough for the CI fast tier (tiny shapes, interpret
mode):

* **operand liveness** — trace the kernel at representative (B, W)
  signatures with ``jax.make_jaxpr`` and assert the auth-bits and
  role-mask input variables are *live*: reachable by the backward pass
  from the jaxpr outputs.  A dead auth operand is a leak waiting to
  happen, whatever the Python signature promises.

* **mask sensitivity** — run the kernel (interpret mode) and assert the
  output actually responds to the mask: an all-zero role mask must return
  no ids, and with W=2 a role in the *second* word must admit exactly the
  vectors authorized in that word (catches "only word 0 honored"
  truncation bugs that liveness alone cannot see).

``audit_l2_topk()`` audits the real kernel; ``audit_kernel(fn, ...)``
takes any callable with the ``l2_topk`` signature so tests can prove the
audit *fails* on a fixture kernel with the auth operand severed.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

SIG_B, SIG_N, SIG_D, SIG_K = 3, 24, 4, 4


def _live_invars(closed_jaxpr) -> List[bool]:
    """Backward liveness over a ClosedJaxpr: which top-level invars can
    reach an output?  Opaque primitives (pallas_call etc.) conservatively
    need all their inputs; call-like primitives recurse via their
    sub-jaxpr params so a truly dead operand stays dead."""
    import jax.extend.core as jcore

    jaxpr = closed_jaxpr.jaxpr

    def live_set(jx, needed_out: Sequence[bool]) -> set:
        needed = {v for v, n in zip(jx.outvars, needed_out)
                  if n and isinstance(v, jcore.Var)}
        for eqn in reversed(jx.eqns):
            if not any(isinstance(v, jcore.Var) and v in needed
                       for v in eqn.outvars):
                continue
            sub = [p for p in eqn.params.values()
                   if hasattr(p, "jaxpr") or hasattr(p, "eqns")]
            if len(sub) == 1 and eqn.primitive.name in (
                    "pjit", "closed_call", "custom_jvp_call",
                    "custom_vjp_call", "remat", "checkpoint"):
                inner = sub[0]
                inner_jaxpr = getattr(inner, "jaxpr", inner)
                out_need = [isinstance(v, jcore.Var) and v in needed
                            for v in eqn.outvars]
                inner_live = live_set(inner_jaxpr, out_need)
                for ov, iv in zip(eqn.invars, inner_jaxpr.invars):
                    if iv in inner_live and isinstance(ov, jcore.Var):
                        needed.add(ov)
            else:
                for v in eqn.invars:
                    if isinstance(v, jcore.Var):
                        needed.add(v)
        return needed

    live = live_set(jaxpr, [True] * len(jaxpr.outvars))
    return [v in live for v in jaxpr.invars]


def _mk_inputs(w: int, rng: np.random.Generator):
    q = rng.standard_normal((SIG_B, SIG_D)).astype(np.float32)
    db = rng.standard_normal((SIG_N, SIG_D)).astype(np.float32)
    if w == 1:
        auth = np.full(SIG_N, 0xFFFFFFFF, np.uint32)
        mask = np.full(SIG_B, 0xFFFFFFFF, np.uint32)
    else:
        auth = np.full((SIG_N, w), 0xFFFFFFFF, np.uint32)
        mask = np.full((SIG_B, w), 0xFFFFFFFF, np.uint32)
    return q, db, auth, mask


def _mk_pred_inputs(p: int, rng: np.random.Generator):
    """(N, P) attribute words with bit 3 of the LAST word set on even rows
    only — the audit's known-selectivity plane."""
    attr = np.zeros((SIG_N, p), np.uint32)
    attr[::2, p - 1] = 1 << 3
    return attr


def audit_kernel(fn: Callable, widths: Sequence[int] = (1, 2),
                 check_semantics: bool = True,
                 pred_widths: Sequence[int] = ()) -> Dict:
    """Audit ``fn`` (an ``l2_topk``-signature callable).  Returns
    ``{"ok": bool, "checks": [{name, ok, detail}, ...]}``.

    ``pred_widths`` additionally audits the predicate-word plane at each
    given P: the attr/require/forbid operands must be live in the traced
    computation, and the output must respond to them (an unsatisfiable
    require returns no ids; a last-word require admits exactly the rows
    holding the bit in that word — catching truncation to word 0)."""
    import jax

    rng = np.random.default_rng(0)
    checks: List[Dict] = []

    def record(name: str, ok: bool, detail: str = "") -> None:
        checks.append({"name": name, "ok": bool(ok), "detail": detail})

    for w in widths:
        q, db, auth, mask = _mk_inputs(w, rng)
        name = f"liveness(B={SIG_B},W={w})"
        try:
            jaxpr = jax.make_jaxpr(
                lambda q, db, a, m: fn(q, db, a, m, SIG_K))(q, db, auth,
                                                            mask)
            live = _live_invars(jaxpr)
            # invars: queries, db, auth_bits, role_mask
            dead = [n for i, n in ((2, "auth_bits"), (3, "role_mask"))
                    if i < len(live) and not live[i]]
            record(name, not dead,
                   f"dead operand(s): {dead}" if dead else
                   "auth_bits and role_mask are live in the traced "
                   "computation")
        except Exception as e:  # trace failure is an audit failure
            record(name, False, f"trace failed: {type(e).__name__}: {e}")

    if check_semantics:
        for w in widths:
            q, db, auth, mask = _mk_inputs(w, rng)
            name = f"zero-mask(B={SIG_B},W={w})"
            try:
                _, ids = fn(q, db, auth, np.zeros_like(mask), SIG_K)
                ids = np.asarray(ids)
                record(name, bool((ids == -1).all()),
                       "all ids are -1 under an all-zero role mask"
                       if (ids == -1).all() else
                       f"zero role mask still returned ids {ids.tolist()}")
            except Exception as e:
                record(name, False, f"run failed: {type(e).__name__}: {e}")
        # word sensitivity: auth only in word 1 (roles >= 32); a query
        # masked in word 1 must see hits, a word-0 query must not
        if 2 in widths:
            q, db, auth, mask = _mk_inputs(2, rng)
            auth = np.zeros_like(auth)
            auth[:, 1] = 1 << 8          # every vector holds role 40 only
            m_hit = np.zeros_like(mask)
            m_hit[:, 1] = 1 << 8         # query as role 40
            m_miss = np.zeros_like(mask)
            m_miss[:, 0] = 1 << 8        # query as role 8 (word 0)
            name = "word-sensitivity(W=2)"
            try:
                _, ids_hit = fn(q, db, auth, m_hit, SIG_K)
                _, ids_miss = fn(q, db, auth, m_miss, SIG_K)
                ids_hit = np.asarray(ids_hit)
                ids_miss = np.asarray(ids_miss)
                ok = bool((ids_hit >= 0).all() and (ids_miss == -1).all())
                record(name, ok,
                       "second auth word is honored" if ok else
                       f"word-1 query ids {ids_hit.tolist()}, word-0 "
                       f"query ids {ids_miss.tolist()} — auth words "
                       "beyond word 0 are not consumed correctly")
            except Exception as e:
                record(name, False, f"run failed: {type(e).__name__}: {e}")

    for p in pred_widths:
        q, db, auth, mask = _mk_inputs(1, rng)
        attr = _mk_pred_inputs(p, rng)
        req = np.zeros((SIG_B, p), np.uint32)
        req[:, p - 1] = 1 << 3
        forb = np.zeros((SIG_B, p), np.uint32)
        name = f"pred-liveness(P={p})"
        try:
            jaxpr = jax.make_jaxpr(
                lambda q, db, a, m, at, r, f: fn(
                    q, db, a, m, SIG_K, attr_bits=at, require=r, forbid=f)
            )(q, db, auth, mask, attr, req, forb)
            live = _live_invars(jaxpr)
            # invars: queries, db, auth_bits, role_mask, attr, require, forbid
            dead = [n for i, n in ((4, "attr_bits"), (5, "require"),
                                   (6, "forbid"))
                    if i < len(live) and not live[i]]
            record(name, not dead,
                   f"dead operand(s): {dead}" if dead else
                   "attr_bits, require, and forbid are live in the traced "
                   "computation")
        except Exception as e:
            record(name, False, f"trace failed: {type(e).__name__}: {e}")
        if not check_semantics:
            continue
        name = f"pred-sensitivity(P={p})"
        try:
            # unsatisfiable require: a bit no attribute row holds
            impossible = np.zeros((SIG_B, p), np.uint32)
            impossible[:, 0] = 1 << 30
            _, ids_none = fn(q, db, auth, mask, SIG_K, attr_bits=attr,
                             require=impossible, forbid=forb)
            # last-word require: exactly the even rows qualify
            _, ids_even = fn(q, db, auth, mask, SIG_K, attr_bits=attr,
                             require=req, forbid=forb)
            # same bit demanded in word 0 instead (P>1): nothing qualifies
            ok_word = True
            if p > 1:
                wrong = np.zeros((SIG_B, p), np.uint32)
                wrong[:, 0] = 1 << 3
                _, ids_wrong = fn(q, db, auth, mask, SIG_K, attr_bits=attr,
                                  require=wrong, forbid=forb)
                ok_word = bool((np.asarray(ids_wrong) == -1).all())
            ids_none = np.asarray(ids_none)
            ids_even = np.asarray(ids_even)
            valid = ids_even[ids_even >= 0]
            ok = (bool((ids_none == -1).all())
                  and len(valid) > 0
                  and bool((valid % 2 == 0).all())
                  and ok_word)
            record(name, ok,
                   "predicate words drive the result" if ok else
                   f"unsat require ids {ids_none.tolist()}, last-word "
                   f"require ids {ids_even.tolist()} — predicate words "
                   "are not consumed correctly")
        except Exception as e:
            record(name, False, f"run failed: {type(e).__name__}: {e}")

    return {"ok": all(c["ok"] for c in checks), "checks": checks,
            "signature": {"b": SIG_B, "n": SIG_N, "d": SIG_D, "k": SIG_K,
                          "widths": list(widths),
                          "pred_widths": list(pred_widths)}}


def audit_l2_topk(widths: Sequence[int] = (1, 2),
                  pred_widths: Sequence[int] = (1, 2)) -> Dict:
    """Audit the real kernel wrapper (interpret mode — CI-safe)."""
    from repro.kernels.l2_topk.ops import l2_topk
    return audit_kernel(l2_topk, widths=widths, pred_widths=pred_widths)


def severed_auth_fixture() -> Callable:
    """An ``l2_topk``-signature kernel that ignores its auth operands —
    the audit must fail on it (tests/test_authlint.py)."""
    import jax
    import jax.numpy as jnp

    def bad_l2_topk(queries, db, auth_bits, role_mask, k, bound=None):
        q = jnp.asarray(queries, jnp.float32)
        dbj = jnp.asarray(db, jnp.float32)
        d = (jnp.sum(q * q, -1)[:, None] - 2.0 * q @ dbj.T
             + jnp.sum(dbj * dbj, -1)[None, :])
        dists, ids = jax.lax.top_k(-d, k)
        return -dists, ids.astype(jnp.int32)

    return bad_l2_topk


def severed_predicate_fixture() -> Callable:
    """An ``l2_topk``-signature kernel that honors auth but ignores the
    predicate-word operands — ``audit_kernel(..., pred_widths=...)`` must
    fail on it (tests/test_authlint.py)."""
    from repro.kernels.l2_topk.ref import l2_topk_ref

    def bad_filtered_l2_topk(queries, db, auth_bits, role_mask, k,
                             bound=None, attr_bits=None, require=None,
                             forbid=None):
        # predicate operands accepted, silently dropped: the exact leak
        # shape the jaxpr audit exists to catch
        b = (np.full(len(queries), np.inf, np.float32) if bound is None
             else bound)
        return l2_topk_ref(queries, db, auth_bits, role_mask, b, k)

    return bad_filtered_l2_topk
