"""Pallas l2_topk kernel vs pure-jnp oracle: shape/dtype/bound sweeps."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.l2_topk import l2_topk, l2_topk_ref, L2TopKConfig


def _case(B, N, d, k, seed=0, role_bit=3, bound=None, cfg=None):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(0, 2 ** 16, size=N).astype(np.uint32)
    role = np.uint32(1 << role_bit)
    cfg = cfg or L2TopKConfig()
    dk, ik = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), role, k,
                     bound=bound, config=cfg)
    dr, ir = l2_topk_ref(jnp.array(q), jnp.array(db), jnp.array(auth),
                         jnp.uint32(role),
                         jnp.float32(np.inf if bound is None else bound), k)
    return np.array(dk), np.array(ik), np.array(dr), np.array(ir)


@pytest.mark.parametrize("B,N,d,k", [
    (1, 100, 8, 1),
    (3, 513, 17, 5),        # unaligned everything
    (8, 2048, 64, 10),
    (5, 1000, 48, 32),
    (2, 4096, 128, 10),
])
def test_matches_ref(B, N, d, k):
    dk, ik, dr, ir = _case(B, N, d, k)
    assert (ik == ir).all()
    finite = np.isfinite(dr)
    np.testing.assert_allclose(dk[finite], dr[finite], rtol=1e-4, atol=1e-4)


def test_bound_pruning_matches_ref():
    # midpoint bound avoids float boundary ties
    dk, ik, dr, ir = _case(4, 600, 24, 8)
    bound = float((dr[0, 3] + dr[0, 4]) / 2)
    dk2, ik2, dr2, ir2 = _case(4, 600, 24, 8, bound=bound)
    assert (ik2 == ir2).all()


def test_no_authorized_vectors_gives_empty():
    rng = np.random.default_rng(3)
    q = rng.standard_normal((2, 16)).astype(np.float32)
    db = rng.standard_normal((64, 16)).astype(np.float32)
    auth = np.zeros(64, np.uint32)           # nobody authorized
    d, i = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth),
                   np.uint32(1), 5)
    assert (np.array(i) == -1).all()
    assert np.isinf(np.array(d)).all()


def test_k_larger_than_authorized():
    rng = np.random.default_rng(4)
    q = rng.standard_normal((1, 8)).astype(np.float32)
    db = rng.standard_normal((100, 8)).astype(np.float32)
    auth = np.zeros(100, np.uint32)
    auth[:3] = 1
    d, i = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth),
                   np.uint32(1), 10)
    i = np.array(i)[0]
    assert (i[:3] >= 0).all() and (i[3:] == -1).all()
    assert set(i[:3]) <= {0, 1, 2}


@pytest.mark.parametrize("bq,bn", [(4, 128), (8, 512), (16, 256)])
def test_tile_shape_invariance(bq, bn):
    cfg = L2TopKConfig(bq=bq, bn=bn)
    dk, ik, dr, ir = _case(6, 700, 32, 7, cfg=cfg)
    assert (ik == ir).all()


def test_per_query_role_masks_match_ref():
    """(B,) role-mask vector: each query row filters by its own role bits."""
    rng = np.random.default_rng(6)
    B, N, d, k = 6, 700, 24, 8
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(0, 2 ** 8, size=N).astype(np.uint32)
    masks = (np.uint32(1) << rng.integers(0, 8, size=B).astype(np.uint32))
    dk, ik = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth),
                     masks.astype(np.uint32), k)
    dr, ir = l2_topk_ref(jnp.array(q), jnp.array(db), jnp.array(auth),
                         jnp.asarray(masks, jnp.uint32),
                         jnp.float32(np.inf), k)
    assert (np.array(ik) == np.array(ir)).all()
    # every returned id is authorized for ITS row's role, not another row's
    for row, m in zip(np.array(ik), masks):
        for v in row[row >= 0]:
            assert auth[v] & m


def test_per_query_bounds_match_ref():
    """(B,) bound vector: each row prunes at its own k-th distance."""
    rng = np.random.default_rng(7)
    B, N, d, k = 4, 600, 24, 8
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(0, 2 ** 16, size=N).astype(np.uint32)
    role = np.uint32(1 << 3)
    # unbounded reference distances give each row its own midpoint bound
    # (between the row-th and row+1-th neighbour — avoids float ties);
    # row 0 stays unbounded
    dr, _ = l2_topk_ref(jnp.array(q), jnp.array(db), jnp.array(auth),
                        jnp.uint32(role), jnp.float32(np.inf), k)
    dr = np.array(dr)
    bounds = np.full(B, np.inf, np.float32)
    for row in range(1, B):
        bounds[row] = (dr[row, row] + dr[row, row + 1]) / 2
    dk2, ik2 = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), role, k,
                       bound=bounds)
    dr2, ir2 = l2_topk_ref(jnp.array(q), jnp.array(db), jnp.array(auth),
                           jnp.uint32(role), jnp.asarray(bounds), k)
    assert (np.array(ik2) == np.array(ir2)).all()
    # a bound between neighbours r and r+1 keeps exactly r+1; row 0 a full k
    assert (np.array(ik2)[0] >= 0).all()
    for row in range(1, B):
        assert (np.array(ik2)[row] >= 0).sum() == row + 1


def test_vector_args_equal_scalar_args():
    """A constant (B,) vector must reproduce the scalar fast path bit-exactly."""
    rng = np.random.default_rng(8)
    B, N, d, k = 5, 300, 16, 6
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(0, 2 ** 8, size=N).astype(np.uint32)
    ds, is_ = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth),
                      np.uint32(4), k, bound=9.0)
    dv, iv = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth),
                     np.full(B, 4, np.uint32), k,
                     bound=np.full(B, 9.0, np.float32))
    assert (np.array(is_) == np.array(iv)).all()
    assert (np.array(ds) == np.array(dv)).all()


def test_per_query_masks_with_k_exceeding_authorized():
    """B>1, mixed roles, k > n_authorized for some rows: -1/inf padding is
    per-row, driven by that row's mask."""
    rng = np.random.default_rng(9)
    B, N, d, k = 3, 200, 8, 10
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = np.zeros(N, np.uint32)
    auth[:3] = 1            # role bit 0: 3 vectors
    auth[3:8] |= 2          # role bit 1: 5 vectors
    masks = np.array([1, 2, 4], np.uint32)   # row 2's role matches nothing
    d_, i_ = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), masks, k)
    dr, ir = l2_topk_ref(jnp.array(q), jnp.array(db), jnp.array(auth),
                         jnp.asarray(masks), jnp.float32(np.inf), k)
    i_ = np.array(i_)
    assert (i_ == np.array(ir)).all()
    assert (i_[0] >= 0).sum() == 3 and set(i_[0][:3]) <= {0, 1, 2}
    assert (i_[1] >= 0).sum() == 5 and set(i_[1][:5]) <= {3, 4, 5, 6, 7}
    assert (i_[2] == -1).all()


# ------------------------------------------------- multi-word auth masks
def _word_mask(roles, W):
    out = np.zeros(W, np.uint32)
    for r in roles:
        out[r // 32] |= np.uint32(1) << np.uint32(r % 32)
    return out


def _mw_case(B, N, d, k, W, seed=0, bound=None, cfg=None):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(0, 2 ** 16, size=(N, W)).astype(np.uint32)
    roles = rng.integers(0, 32 * W, size=B)
    masks = np.stack([_word_mask([r], W) for r in roles])
    dk, ik = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), masks, k,
                     bound=bound, config=cfg or L2TopKConfig())
    dr, ir = l2_topk_ref(jnp.array(q), jnp.array(db), jnp.array(auth),
                         jnp.asarray(masks),
                         jnp.float32(np.inf if bound is None else bound), k)
    return (np.array(dk), np.array(ik), np.array(dr), np.array(ir),
            auth, masks)


@pytest.mark.parametrize("B,N,d,k,W", [
    (3, 513, 17, 5, 2),      # unaligned everything, 64-role universe
    (6, 700, 24, 8, 2),
    (5, 300, 16, 6, 8),      # 256-role universe
    (1, 100, 8, 1, 3),
])
def test_multi_word_matches_ref(B, N, d, k, W):
    dk, ik, dr, ir, auth, masks = _mw_case(B, N, d, k, W)
    assert (ik == ir).all()
    finite = np.isfinite(dr)
    np.testing.assert_allclose(dk[finite], dr[finite], rtol=1e-4, atol=1e-4)
    # every hit authorized for ITS row's word mask
    for row, m in zip(ik, masks):
        for v in row[row >= 0]:
            assert (auth[v] & m).any()


def test_multi_word_padding_semantics():
    """Padded db rows carry all-zero auth words and padded query rows
    all-zero masks: results on unaligned operands equal the same search over
    explicitly padded operands, and no padding row/id ever surfaces."""
    rng = np.random.default_rng(20)
    B, N, d, k, W = 5, 700, 24, 8, 2       # B % bq != 0, N % bn != 0
    cfg = L2TopKConfig(bq=8, bn=512)
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(1, 2 ** 16, size=(N, W)).astype(np.uint32)
    masks = np.stack([_word_mask([r], W)
                      for r in rng.integers(0, 32 * W, size=B)])
    d1, i1 = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), masks, k,
                     config=cfg)
    i1 = np.array(i1)
    assert (i1 < N).all()                  # no padded db id surfaces
    # explicit padding with all-zero auth words / all-zero mask rows must
    # reproduce the implicit padding bit-exactly
    Npad, Bpad = 1024, 8
    dbp = np.zeros((Npad, d), np.float32)
    dbp[:N] = db
    authp = np.zeros((Npad, W), np.uint32)   # zero words: never authorized
    authp[:N] = auth
    qp = np.zeros((Bpad, d), np.float32)
    qp[:B] = q
    maskp = np.zeros((Bpad, W), np.uint32)   # zero masks: nothing authorized
    maskp[:B] = masks
    d2, i2 = l2_topk(jnp.array(qp), jnp.array(dbp), jnp.array(authp), maskp,
                     k, config=cfg)
    assert (np.array(i2)[:B] == i1).all()
    assert (np.array(i2)[B:] == -1).all()    # zero-mask rows return nothing
    assert (np.array(d1) == np.array(d2)[:B]).all()


def test_single_word_shapes_bit_exact():
    """(N, 1) auth + (B, 1) masks must reproduce the legacy (N,) + (B,)
    single-word kernel path bit-exactly (W == 1 dispatch)."""
    rng = np.random.default_rng(21)
    B, N, d, k = 6, 700, 24, 8
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(0, 2 ** 16, size=N).astype(np.uint32)
    masks = (np.uint32(1) << rng.integers(0, 16, size=B).astype(np.uint32))
    d1, i1 = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), masks, k,
                     bound=9.0)
    d2, i2 = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth[:, None]),
                     masks[:, None], k, bound=9.0)
    assert (np.array(i1) == np.array(i2)).all()
    assert (np.array(d1) == np.array(d2)).all()


def test_word_boundary_roles_do_not_alias():
    """Roles 31/32/33/63/64 in one batch: each row only sees vectors tagged
    with its exact role — bit 33 must not admit role-1 vectors (the old
    single-word `1 << (r % 32)` wraparound did exactly that)."""
    roles = [1, 31, 32, 33, 63, 64]
    W = 3
    rng = np.random.default_rng(22)
    B, N, d, k = len(roles), 300, 8, 10
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    vec_roles = np.asarray(roles)[rng.integers(0, len(roles), size=N)]
    auth = np.stack([_word_mask([r], W) for r in vec_roles])
    masks = np.stack([_word_mask([r], W) for r in roles])
    d_, i_ = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), masks, k)
    i_ = np.array(i_)
    dr, ir = l2_topk_ref(jnp.array(q), jnp.array(db), jnp.array(auth),
                         jnp.asarray(masks), jnp.float32(np.inf), k)
    assert (i_ == np.array(ir)).all()
    for row, r in zip(i_, roles):
        got = row[row >= 0]
        assert len(got)                      # every role has vectors here
        assert (vec_roles[got] == r).all()   # and sees ONLY its own


def test_scalar_mask_rejected_for_multi_word_auth():
    """A bare scalar role mask cannot address roles >= 32: multi-word auth
    requires all-W-words mask operands (hard error, never silent)."""
    rng = np.random.default_rng(23)
    q = rng.standard_normal((2, 8)).astype(np.float32)
    db = rng.standard_normal((64, 8)).astype(np.float32)
    auth = np.ones((64, 2), np.uint32)
    with pytest.raises(ValueError):
        l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth),
                np.uint32(1), 5)


def test_multi_role_mask():
    """A multi-role query ORs role bits — union semantics in-kernel."""
    rng = np.random.default_rng(5)
    q = rng.standard_normal((2, 16)).astype(np.float32)
    db = rng.standard_normal((256, 16)).astype(np.float32)
    auth = rng.integers(0, 8, size=256).astype(np.uint32)  # bits 0..2
    both = np.uint32(0b011)
    d, i = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), both, 10)
    i = np.array(i)
    ok = (auth & 0b011) != 0
    for row in i:
        for v in row[row >= 0]:
            assert ok[v]


# --------------------------------------------------------------------------
# predicate-word plane (hybrid filtered search)
# --------------------------------------------------------------------------
def _pred_case(B, N, d, k, P, seed=0, cfg=None, density=0.5):
    """Random auth + random (N, P) attribute words + per-row require/forbid
    rows; returns kernel and ref outputs plus the host-side truth masks."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(1, 2 ** 16, size=N).astype(np.uint32)
    role = np.uint32(1 << 3)
    attr = (rng.random((N, P * 32)) < density)
    req_bits = np.zeros((B, P * 32), bool)
    forb_bits = np.zeros((B, P * 32), bool)
    for row in range(B):
        req_bits[row, rng.integers(0, P * 32)] = True
        forb_bits[row, rng.integers(0, P * 32)] = True
    forb_bits &= ~req_bits

    def pack(bits):
        words = np.zeros((len(bits), P), np.uint32)
        for j in range(bits.shape[1]):
            words[:, j // 32] |= bits[:, j].astype(np.uint32) << (j % 32)
        return words

    attr_w, req_w, forb_w = pack(attr), pack(req_bits), pack(forb_bits)
    cfg = cfg or L2TopKConfig()
    dk, ik = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), role, k,
                     config=cfg, attr_bits=attr_w, require=req_w,
                     forbid=forb_w)
    dr, ir = l2_topk_ref(jnp.array(q), jnp.array(db), jnp.array(auth),
                         jnp.uint32(role), jnp.float32(np.inf), k,
                         attr_bits=attr_w, require=req_w, forbid=forb_w)
    pred_ok = np.stack([
        (attr[:, req_bits[row]].all(axis=1) if req_bits[row].any()
         else np.ones(N, bool))
        & ~(attr[:, forb_bits[row]].any(axis=1))
        for row in range(B)])
    return (np.array(dk), np.array(ik), np.array(dr), np.array(ir),
            (auth & role) != 0, pred_ok)


@pytest.mark.parametrize("B,N,d,k,P", [
    (3, 513, 17, 5, 1),      # unaligned everything
    (6, 700, 24, 8, 2),
    (1, 100, 8, 1, 2),
])
def test_predicate_matches_ref(B, N, d, k, P):
    dk, ik, dr, ir, auth_ok, pred_ok = _pred_case(B, N, d, k, P)
    assert (ik == ir).all()
    finite = np.isfinite(dr)
    np.testing.assert_allclose(dk[finite], dr[finite], rtol=1e-4, atol=1e-4)
    # every hit satisfies auth AND its row's predicate conjunction
    for row, hits in enumerate(ik):
        for v in hits[hits >= 0]:
            assert auth_ok[v] and pred_ok[row, v]


def _pallas_invars(jaxpr):
    out = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(len(eqn.invars))
            for p in eqn.params.values():
                if hasattr(p, "jaxpr"):
                    walk(getattr(p.jaxpr, "jaxpr", p.jaxpr))
                elif hasattr(p, "eqns"):
                    walk(p)
    walk(jaxpr.jaxpr)
    return out


def test_p0_operands_take_the_exact_existing_path():
    """No-predicate calls are pinned to the pre-predicate kernel: the traced
    jaxpr is byte-identical whether the predicate kwargs are omitted or
    explicitly None, the pallas_call carries the original 8 operands (a
    predicate plane adds 3), and outputs are bit-equal to an all-pass
    predicate run."""
    import jax
    rng = np.random.default_rng(30)
    B, N, d, k = 4, 600, 24, 8
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(0, 2 ** 16, size=N).astype(np.uint32)
    role = np.uint32(1 << 3)
    j_plain = jax.make_jaxpr(
        lambda q, db, a: l2_topk(q, db, a, role, k))(q, db, auth)
    j_none = jax.make_jaxpr(
        lambda q, db, a: l2_topk(q, db, a, role, k, attr_bits=None,
                                 require=None, forbid=None))(q, db, auth)
    assert str(j_plain) == str(j_none)
    assert _pallas_invars(j_plain) == [8]
    attr = rng.integers(0, 2 ** 8, size=(N, 1)).astype(np.uint32)
    j_pred = jax.make_jaxpr(
        lambda q, db, a, at, r, f: l2_topk(q, db, a, role, k, attr_bits=at,
                                           require=r, forbid=f))(
        q, db, auth, attr, np.zeros((B, 1), np.uint32),
        np.zeros((B, 1), np.uint32))
    assert _pallas_invars(j_pred) == [11]
    # all-pass predicate (require=0, forbid=0) equals the unfiltered run
    d0, i0 = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), role, k)
    d1, i1 = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), role, k,
                     attr_bits=attr, require=np.zeros((B, 1), np.uint32),
                     forbid=np.zeros((B, 1), np.uint32))
    assert (np.array(i0) == np.array(i1)).all()
    assert (np.array(d0) == np.array(d1)).all()


def test_predicate_padding_semantics():
    """Padded db rows carry all-zero attribute words, so they fail every
    nonzero require; padded query rows carry all-zero require/forbid.
    Results on unaligned operands equal the same search over explicitly
    padded operands bit-exactly, and no padding id ever surfaces."""
    rng = np.random.default_rng(31)
    B, N, d, k, P = 5, 700, 24, 8, 1       # B % bq != 0, N % bn != 0
    cfg = L2TopKConfig(bq=8, bn=512)
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = rng.integers(1, 2 ** 16, size=(N,)).astype(np.uint32)
    role = np.uint32(1 << 2)
    attr = rng.integers(1, 2 ** 8, size=(N, P)).astype(np.uint32)
    req = np.zeros((B, P), np.uint32)
    req[:, 0] = 1 << 2                      # nonzero require for every row
    forb = np.zeros((B, P), np.uint32)
    d1, i1 = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), role, k,
                     config=cfg, attr_bits=attr, require=req, forbid=forb)
    i1 = np.array(i1)
    assert (i1 < N).all()                  # no padded db id surfaces
    Npad, Bpad = 1024, 8
    dbp = np.zeros((Npad, d), np.float32)
    dbp[:N] = db
    authp = np.zeros(Npad, np.uint32)
    authp[:N] = auth
    attrp = np.zeros((Npad, P), np.uint32)  # zero words: fail the require
    attrp[:N] = attr
    qp = np.zeros((Bpad, d), np.float32)
    qp[:B] = q
    reqp = np.zeros((Bpad, P), np.uint32)   # zero require/forbid: all-pass
    reqp[:B] = req
    forbp = np.zeros((Bpad, P), np.uint32)
    maskp = np.zeros(Bpad, np.uint32)       # zero role mask: no results
    maskp[:B] = role
    d2, i2 = l2_topk(jnp.array(qp), jnp.array(dbp), jnp.array(authp), maskp,
                     k, config=cfg, attr_bits=attrp, require=reqp,
                     forbid=forbp)
    assert (np.array(i2)[:B] == i1).all()
    assert (np.array(i2)[B:] == -1).all()
    assert (np.array(d1) == np.array(d2)[:B]).all()


def test_predicate_word_boundary_does_not_alias():
    """P=2: attribute bit 35 (word 1, bit 3) and bit 3 (word 0) are distinct
    — a require on one must never admit rows tagged only with the other
    (the predicate dual of the role-word aliasing regression)."""
    rng = np.random.default_rng(32)
    B, N, d, k = 2, 300, 8, 10
    q = rng.standard_normal((B, d)).astype(np.float32)
    db = rng.standard_normal((N, d)).astype(np.float32)
    auth = np.ones(N, np.uint32)
    role = np.uint32(1)
    tag_word1 = rng.random(N) < 0.5         # rows holding bit 35 only
    attr = np.zeros((N, 2), np.uint32)
    attr[tag_word1, 1] = 1 << 3
    attr[~tag_word1, 0] = 1 << 3            # others hold bit 3 only
    req_w1 = np.zeros((B, 2), np.uint32)
    req_w1[:, 1] = 1 << 3
    req_w0 = np.zeros((B, 2), np.uint32)
    req_w0[:, 0] = 1 << 3
    forb = np.zeros((B, 2), np.uint32)
    for req, want in ((req_w1, tag_word1), (req_w0, ~tag_word1)):
        dk, ik = l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), role,
                         k, attr_bits=attr, require=req, forbid=forb)
        dr, ir = l2_topk_ref(jnp.array(q), jnp.array(db), jnp.array(auth),
                             jnp.uint32(role), jnp.float32(np.inf), k,
                             attr_bits=attr, require=req, forbid=forb)
        ik = np.array(ik)
        assert (ik == np.array(ir)).all()
        for row in ik:
            got = row[row >= 0]
            assert len(got)
            assert want[got].all()          # only its own word's rows


def test_predicate_rows_without_attr_plane_rejected():
    """require/forbid against a call with no attr_bits is a hard error —
    never a silently unfiltered answer."""
    rng = np.random.default_rng(33)
    q = rng.standard_normal((2, 8)).astype(np.float32)
    db = rng.standard_normal((64, 8)).astype(np.float32)
    auth = np.ones(64, np.uint32)
    with pytest.raises(ValueError):
        l2_topk(jnp.array(q), jnp.array(db), jnp.array(auth), np.uint32(1),
                5, require=np.zeros((2, 1), np.uint32))


def test_kernel_mode_follows_the_backend(monkeypatch):
    """The backend alone picks the kernel mode: the CPU backend interprets
    the kernel, TPU compiles it, and any other backend is refused before a
    launch — never a silent fallback."""
    from repro.kernels.l2_topk import ops
    seen = []
    inner = ops.l2_topk_pallas

    def spy(*args, **kw):
        seen.append(kw["interpret"])
        return inner(*args, **kw)

    monkeypatch.setattr(ops, "l2_topk_pallas", spy)
    rng = np.random.default_rng(40)
    q = rng.standard_normal((2, 8)).astype(np.float32)
    db = rng.standard_normal((64, 8)).astype(np.float32)
    auth = np.ones(64, np.uint32)
    d, i = l2_topk(q, db, auth, np.uint32(1), 3)
    assert seen == [True] and (np.asarray(i) >= 0).all()
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.kernel_interpret() is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        l2_topk(q, db, auth, np.uint32(1), 3)
    assert seen == [True]


def test_importing_repro_allocates_no_device_array():
    """Importing any repro module leaves no JAX array behind: an array made
    at import would claim the default device (the chip) in every process
    that only imports the library."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = (
        "import importlib, pkgutil, jax, repro\n"
        "names = [m.name for m in pkgutil.walk_packages(repro.__path__, "
        "'repro.')]\n"
        "for n in names: importlib.import_module(n)\n"
        "from repro.kernels.l2_topk import ref\n"
        "assert isinstance(ref.INF, float)\n"
        "live = jax.live_arrays()\n"
        "assert not live, [a.shape for a in live]\n"
        "print(len(names))\n")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert int(r.stdout.split()[-1]) > 50
