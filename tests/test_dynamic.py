"""Appendix I: inserts, deletes, grants/revocations without a rebuild —
now routed through the unified ``store.search`` entry point, including the
batched ScoreScan path, tombstone-aware over-fetch, fresh leftover blocks
for unseen role combinations, and n_roles > 32 multi-word auth masks."""
import numpy as np
import pytest

from repro.core import (build_effveda, build_vector_storage, exact_factory,
                        generate_policy, metrics, HNSWCostModel)
from repro.core.dynamic import DynamicStore


@pytest.fixture()
def dyn(small_policy, small_vectors, cost_model):
    res = build_effveda(small_policy, cost_model, beta=1.1, k=10)
    store = build_vector_storage(res, small_vectors.copy(),
                                 engine_factory=exact_factory())
    return DynamicStore(store, cost_model)


def _truth(dyn, x, r, k):
    mask = dyn.store.authorized_mask(r).copy()
    for t in dyn.tombstones:
        mask[t] = False
    return [i for _, i in metrics.brute_force_topk(dyn.store.data, mask,
                                                   x, k)]


def test_insert_becomes_searchable(dyn, small_policy):
    rng = np.random.default_rng(0)
    r = 2
    v = rng.standard_normal(16).astype(np.float32)
    vid = dyn.insert(v, frozenset({r}))
    got = dyn.search(v, r, k=5)
    assert got and got[0][1] == vid            # nearest to itself
    # other roles must NOT see it
    other = (r + 1) % small_policy.n_roles
    got2 = dyn.search(v, other, k=5)
    assert all(i != vid for _, i in got2)


def test_delete_disappears(dyn, small_policy):
    r = 1
    ids = small_policy.d_of_role(r)
    victim = int(ids[0])
    x = dyn.store.data[victim]
    before = dyn.search(x, r, k=5)
    assert before[0][1] == victim
    dyn.delete(victim)
    after = dyn.search(x, r, k=5)
    assert all(i != victim for _, i in after)
    assert [i for _, i in after] == _truth(dyn, x, r, 5)


def test_grant_and_revoke_move_visibility(dyn, small_policy):
    r_from, r_to = 0, 3
    only_from = [int(v) for v in small_policy.d_of_role(r_from)
                 if not small_policy.authorized_mask(r_to)[v]]
    vid = only_from[0]
    x = dyn.store.data[vid]
    assert all(i != vid for _, i in dyn.search(x, r_to, k=5))
    dyn.grant(vid, r_to)
    assert dyn.search(x, r_to, k=5)[0][1] == vid      # now visible
    dyn.revoke(vid, r_to)
    assert all(i != vid for _, i in dyn.search(x, r_to, k=5))
    # original role kept access throughout
    assert dyn.search(x, r_from, k=5)[0][1] == vid


def test_correctness_after_mixed_churn(dyn, small_policy):
    rng = np.random.default_rng(1)
    for i in range(20):
        op = i % 3
        if op == 0:
            tau = frozenset({int(rng.integers(small_policy.n_roles))})
            dyn.insert(rng.standard_normal(16).astype(np.float32), tau)
        elif op == 1:
            alive = [v for v in range(len(dyn.store.data))
                     if v not in dyn.tombstones]
            dyn.delete(int(rng.choice(alive)))
        else:
            alive = [v for v in range(len(dyn.store.data))
                     if v not in dyn.tombstones]
            dyn.grant(int(rng.choice(alive)),
                      int(rng.integers(small_policy.n_roles)))
    for _ in range(10):
        r = int(rng.integers(small_policy.n_roles))
        x = rng.standard_normal(16).astype(np.float32)
        got = [i for _, i in dyn.search(x, r, k=8)]
        assert got == _truth(dyn, x, r, 8)[:len(got)]


# ------------------------------------------------- unified API + satellites
def _scan_dyn(seed):
    from repro.ann.scorescan import scorescan_factory
    policy = generate_policy(n_vectors=1200, n_roles=8, n_permissions=20,
                             seed=seed)
    rng = np.random.default_rng(4)
    vecs = rng.standard_normal((policy.n_vectors, 16)).astype(np.float32)
    cm = HNSWCostModel(lam_threshold=100)
    res = build_effveda(policy, cm, beta=1.1, k=10)
    store = build_vector_storage(res, vecs,
                                 engine_factory=scorescan_factory(policy))
    return DynamicStore(store, cm)


@pytest.fixture()
def scan_dyn():
    """ScoreScan-engine dynamic store: mutations rebuild MaskedEngines with
    fresh auth bits and queries take the batched kernel path."""
    return _scan_dyn(3)


@pytest.fixture()
def scan_dyn_shared():
    """``scan_dyn`` built from a policy whose nodes each hold blocks one
    grant apart, so a grant or revoke can keep a row in its node."""
    return _scan_dyn(5)


def test_scan_store_mutations_through_store_search(scan_dyn):
    """Insert/delete/grant/revoke on a ScoreScan store, then search parity
    vs exact rescan — the dynamic path now rides the batched engine."""
    dyn = scan_dyn
    policy = dyn.store.policy
    rng = np.random.default_rng(5)
    assert dyn.store.batched_capable()
    v_new = rng.standard_normal(16).astype(np.float32)
    vid = dyn.insert(v_new, frozenset({2}))
    assert dyn.search(v_new, 2, k=5)[0][1] == vid
    victim = int(policy.d_of_role(1)[0])
    dyn.delete(victim)
    only0 = [int(v) for v in policy.d_of_role(0)
             if not dyn.store.authorized_mask(3)[v]
             and v not in dyn.tombstones]
    moved = only0[0]
    dyn.grant(moved, 3)
    dyn.revoke(moved, 0)
    for _ in range(8):
        r = int(rng.integers(policy.n_roles))
        x = rng.standard_normal(16).astype(np.float32)
        got = [i for _, i in dyn.search(x, r, k=8)]
        assert got == _truth(dyn, x, r, 8)[:len(got)], r
    # the entry point reports the batched path for this store
    from repro.core import Query
    res = dyn.store.search(Query(vector=x, roles=(0,), k=4))[0]
    assert res.path.startswith("batched")


def test_revoke_purges_stale_copies_from_node_engines(scan_dyn):
    """Regression (code review): revoking a role must not leave the vector's
    row — with auth bits still carrying the revoked role — in node engines
    of the *old* block, where a pure-node search (no post-filter) would
    leak it to the revoked role."""
    dyn = scan_dyn
    # a vector in a multi-role block that lives inside >= 1 node engine
    vid = next(v for v, b in sorted(dyn.vec_block.items())
               if len(dyn.block_roles[b]) >= 2 and dyn._containers(b)[0])
    tau = dyn.block_roles[dyn.vec_block[vid]]
    r = min(tau)
    x = dyn.store.data[vid]
    assert dyn.search(x, r, k=3)[0][1] == vid
    dyn.revoke(vid, r)
    assert all(i != vid for _, i in dyn.search(x, r, k=8)), "leak!"
    got = [i for _, i in dyn.search(x, r, k=8)]
    assert got == _truth(dyn, x, r, 8)[:len(got)]
    # the remaining roles still reach it
    other = next(iter(tau - {r}))
    assert dyn.search(x, other, k=3)[0][1] == vid
    # no stale copy remains outside the new block's containers
    new_b = dyn.vec_block[vid]
    for key, eng in dyn.store.engines.items():
        if new_b not in dyn.store.lattice.nodes[key].blocks:
            assert vid not in set(int(i) for i in eng.ids), key


def test_scan_store_grant_revoke_churn_parity(scan_dyn):
    """Randomized grant/revoke churn on the ScoreScan store: every role's
    searches must match an exact rescan (catches stale rows and stale auth
    bits in shared containers)."""
    dyn = scan_dyn
    policy = dyn.store.policy
    rng = np.random.default_rng(11)
    n = len(dyn.store.data)
    for _ in range(30):
        vid = int(rng.integers(n))
        if vid in dyn.tombstones:
            continue
        r = int(rng.integers(policy.n_roles))
        tau = dyn.block_roles[dyn.vec_block[vid]]
        if r in tau and len(tau) > 1:
            dyn.revoke(vid, r)
        else:
            dyn.grant(vid, r)
    for _ in range(10):
        r = int(rng.integers(policy.n_roles))
        x = rng.standard_normal(16).astype(np.float32)
        got = [i for _, i in dyn.search(x, r, k=8)]
        assert got == _truth(dyn, x, r, 8)[:len(got)], r


def test_in_place_auth_refresh_reaches_the_device_operands(
        scan_dyn_shared):
    """A grant or revoke that keeps a row in a ScoreScan node rewrites the
    row's auth words in place, in the same engine.  That engine's device
    operands, built by an earlier search, must follow before the next
    launch: a launch of the node under the granted role finds the row, one
    after the revoke does not, and every answer matches the exact oracle
    with no unauthorized hit."""
    dyn = scan_dyn_shared
    policy = dyn.store.policy
    pick = None
    for vid in sorted(dyn.vec_block):
        b = dyn.vec_block[vid]
        tau = dyn.block_roles[b]
        old_nodes = set(dyn._containers(b)[0])
        for r in range(policy.n_roles):
            new_tau = frozenset(tau | {r})
            if r in tau or new_tau not in dyn.block_roles:
                continue
            nb = dyn.block_roles.index(new_tau)
            shared = old_nodes & set(dyn._containers(nb)[0])
            if shared:
                pick = (vid, r, tau, sorted(shared, key=str))
                break
        if pick:
            break
    assert pick is not None
    vid, r, tau, shared = pick
    x = dyn.store.data[vid]
    for role in range(policy.n_roles):     # every node uploads its operands
        dyn.search(x, role, k=8)
    engines = [dyn.store.engines[key] for key in shared]
    assert all(e._operands is not None for e in engines)
    mask_r = dyn.store.kernel_role_mask((r,))

    def check(role, k=8):
        got = [i for _, i in dyn.search(x, role, k=k)]
        assert got == _truth(dyn, x, role, k)[:len(got)], role
        assert dyn.store.authorized_mask(role)[got].all(), role
        return got

    dyn.grant(vid, r)
    # the row stayed in these engines: the in-place path, not a rebuild
    assert [dyn.store.engines[key] for key in shared] == engines
    for eng in engines:
        assert eng.search_masked(x, 1, mask_r)[0][1] == vid
    assert check(r)[0] == vid
    for role in tau:
        assert check(role)[0] == vid
    dyn.revoke(vid, r)
    assert [dyn.store.engines[key] for key in shared] == engines
    for eng in engines:
        assert vid not in [i for _, i in eng.search_masked(x, 8, mask_r)]
    assert vid not in check(r)
    for role in tau:
        assert check(role)[0] == vid


def test_unseen_role_combination_makes_fresh_leftover_block(scan_dyn):
    """An insert under a never-seen role combination creates a fresh
    leftover block that every role in the combination can search — and the
    multi-role entry point sees it too."""
    dyn = scan_dyn
    policy = dyn.store.policy
    combo = frozenset(range(policy.n_roles))        # all roles: surely unseen
    assert combo not in dyn.block_roles
    n_blocks_before = len(dyn.block_roles)
    v = np.full(16, 7.0, np.float32)
    vid = dyn.insert(v, combo)
    assert len(dyn.block_roles) == n_blocks_before + 1
    b = dyn.vec_block[vid]
    assert b in dyn.store.leftover_ids               # fresh leftover block
    for r in combo:
        assert b in dyn.store.plans[r].leftover_blocks
        assert dyn.search(v, r, k=3)[0][1] == vid
    got = dyn.search(v, roles=(0, 1), k=3)           # multi-role union
    assert got[0][1] == vid


def test_many_roles_dynamic_store_multi_word_masks(small_vectors):
    """n_roles > 32: auth masks go multi-word (W=2) end-to-end — the packed
    shard now builds instead of refusing, mutations rebuild engines with
    word arrays, and batched searches match the exact oracle for roles on
    both sides of the 32-bit word boundary."""
    from repro.ann.scorescan import scorescan_factory
    policy = generate_policy(n_vectors=1000, n_roles=40, n_permissions=90,
                             seed=6)
    rng = np.random.default_rng(7)
    vecs = rng.standard_normal((policy.n_vectors, 16)).astype(np.float32)
    cm = HNSWCostModel(lam_threshold=80)
    res = build_effveda(policy, cm, beta=1.1, k=10)
    store = build_vector_storage(res, vecs,
                                 engine_factory=scorescan_factory(policy))
    assert store.mask_width == 2
    shard = store.pack_leftover_shard()              # no more refusal
    assert shard is not None and shard.mask_width == 2
    dyn = DynamicStore(store, cm)
    vid = dyn.insert(np.full(16, 3.0, np.float32), frozenset({35}))
    dyn.delete(int(policy.d_of_role(2)[0]))
    from repro.core import Query
    for r in (35, 2, 33):
        x = rng.standard_normal(16).astype(np.float32)
        got = [i for _, i in dyn.search(x, r, k=6)]
        assert got == _truth(dyn, x, r, 6)[:len(got)], r
        res_q = store.search(Query(vector=x, roles=(r,), k=6))[0]
        assert res_q.path.startswith("batched")
        # forcing the packed shard (rebuilt after the mutations) agrees
        res_p = store.search(Query(vector=x, roles=(r,), k=6),
                             packed=True)[0]
        assert res_p.path == "batched+packed"
        assert [i for _, i in res_p.hits] == [i for _, i in res_q.hits], r
    assert dyn.search(np.full(16, 3.0, np.float32), 35, k=1)[0][1] == vid


def test_overfetch_only_counts_authorized_tombstones(dyn, small_policy):
    """Regression (ISSUE satellite): deleting many vectors *outside* the
    querying role's reach must not inflate its over-fetch k at all, while
    in-role deletes still pad exactly."""
    r = 2
    mask = dyn.store.authorized_mask(r).copy()
    out_of_role = [v for v in range(len(dyn.store.data)) if not mask[v]]
    for v in out_of_role[:30]:
        dyn.delete(int(v))
    assert len(dyn.tombstones) == 30
    assert dyn.tombstone_pad((r,)) == 0              # none can surface for r
    x = dyn.store.data[int(small_policy.d_of_role(r)[0])]
    got = [i for _, i in dyn.search(x, r, k=6)]
    assert got == _truth(dyn, x, r, 6)[:len(got)]
    # an in-role delete pads by exactly one
    in_role = [v for v in range(len(dyn.store.data))
               if mask[v] and v not in dyn.tombstones]
    dyn.delete(int(in_role[0]))
    assert dyn.tombstone_pad((r,)) == 1
    got = [i for _, i in dyn.search(x, r, k=6)]
    assert got == _truth(dyn, x, r, 6)[:len(got)]
    # multi-role pad: union semantics
    other = int((r + 1) % small_policy.n_roles)
    assert dyn.tombstone_pad((r, other)) >= dyn.tombstone_pad((r,))


def test_reoptimization_trigger(dyn, small_policy):
    rng = np.random.default_rng(2)
    tau = frozenset({0})
    assert dyn.needs_reoptimization() == []
    for _ in range(60):                      # grow role-0 containers a lot
        dyn.insert(rng.standard_normal(16).astype(np.float32), tau)
    drifted = dyn.needs_reoptimization()
    # containers of role 0's blocks should drift past the slack eventually
    # (some lattices put the block in a big node — then more inserts needed;
    # accept either a trigger or a small store)
    assert isinstance(drifted, list)


# --------------------------------------------- dynamic-path bugfix sweep
def test_emptied_block_still_searchable_for_every_role(scan_dyn):
    """Regression: deleting every member of a node-hosted block crashed
    plan classification (``members[0]`` on the emptied block) on the next
    search.  An empty block contributes nothing either way."""
    dyn = scan_dyn
    policy = dyn.store.policy
    hosted = [b for b in range(len(dyn.block_members))
              if dyn.block_members[b] and dyn._containers(b)[0]]
    b = min(hosted, key=lambda i: len(dyn.block_members[i]))
    for vid in list(dyn.block_members[b]):
        dyn.delete(int(vid))
    assert not dyn.block_members[b]
    rng = np.random.default_rng(21)
    x = rng.standard_normal(16).astype(np.float32)
    for r in range(policy.n_roles):
        got = [i for _, i in dyn.search(x, r, k=6)]
        assert got == _truth(dyn, x, r, 6)[:len(got)], r
    # multi-role query plans walk the same nodes
    roles = tuple(range(policy.n_roles))
    got = [i for _, i in dyn.search(x, roles=roles, k=6)]
    mask = dyn.store.authorized_mask_multi(roles).copy()
    for t in dyn.tombstones:
        mask[t] = False
    want = [i for _, i in metrics.brute_force_topk(dyn.store.data, mask,
                                                   x, 6)]
    assert got == want[:len(got)] and len(got) == len(want)


def test_grant_carries_auth_words_at_insert_time(monkeypatch):
    """Regression: grant/revoke moves inserted into mutable masked engines
    with *no* auth words and patched the mask array afterwards — a window
    where the row was live but invisible (or worse, carrying stale words).
    The words for the new role combination must arrive with insert()."""
    from repro.ann.hnsw import HNSWIndex
    from repro.core import hnsw_masked_factory

    policy = generate_policy(n_vectors=500, n_roles=8, n_permissions=20,
                             seed=8)
    rng = np.random.default_rng(9)
    vecs = rng.standard_normal((policy.n_vectors, 16)).astype(np.float32)
    cm = HNSWCostModel(lam_threshold=60)
    res = build_effveda(policy, cm, beta=1.1, k=10)
    store = build_vector_storage(
        res, vecs, engine_factory=hnsw_masked_factory(policy, M=8, efc=60))
    dyn = DynamicStore(store, cm)

    calls = []
    orig = HNSWIndex.insert

    def spy(self, vid, vec, auth_bits=None, attr_bits=None):
        calls.append((int(vid), auth_bits))
        return orig(self, vid, vec, auth_bits=auth_bits, attr_bits=attr_bits)

    monkeypatch.setattr(HNSWIndex, "insert", spy)

    # a grant whose destination block is node-hosted, so the move takes the
    # in-place MutableEngine path rather than the leftover path
    pick = None
    for vid in sorted(dyn.vec_block):
        tau = dyn.block_roles[dyn.vec_block[vid]]
        for r in range(policy.n_roles):
            if r in tau:
                continue
            new_tau = frozenset(tau | {r})
            if new_tau in dyn.block_roles:
                nb = dyn.block_roles.index(new_tau)
                if dyn._containers(nb)[0]:
                    pick = (vid, r)
                    break
        if pick:
            break
    assert pick is not None
    vid, r = pick
    old_tau = dyn.block_roles[dyn.vec_block[vid]]
    x = np.asarray(dyn.data[vid])
    dyn.grant(vid, r)
    moved = [bits for v, bits in calls if v == vid]
    assert moved and all(bits is not None for bits in moved), \
        "auth words must be passed at insert time, not patched in later"
    # every engine now holding the row carries the NEW combination's words
    new_tau = dyn.block_roles[dyn.vec_block[vid]]
    assert r in new_tau
    checked = 0
    for eng in dyn.store.engines.values():
        if not hasattr(eng, "auth_bits"):
            continue
        idx = np.flatnonzero(np.asarray(eng.ids) == vid)
        if not len(idx) or vid in getattr(eng, "tombstoned", set()):
            continue
        row = np.atleast_1d(eng.auth_bits[int(idx[0])])
        want = np.atleast_1d(dyn._auth_row(eng, new_tau))
        np.testing.assert_array_equal(row, want)
        checked += 1
    assert checked >= 1
    # behavioral: visible to the granted role, still to the old ones, and
    # (auth filtering is exact even though the HNSW beam is approximate)
    # never surfaced once revoked again
    assert dyn.search(x, r, k=3)[0][1] == vid
    for r_old in old_tau:
        assert dyn.search(x, r_old, k=3)[0][1] == vid
    dyn.revoke(vid, r)
    assert all(i != vid for _, i in dyn.search(x, r, k=12))
