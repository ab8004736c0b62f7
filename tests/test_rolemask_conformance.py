"""Property-based access-control conformance harness (ISSUE 4).

Multi-word auth masks lift the 32-role ceiling; this suite is the guard
that NO execution path ever drifts from the authorization ground truth.
For hypothesis-generated role universes up to 256 roles (word boundaries
pinned at 31/32/33 and 63/64 — exactly where the old ``1 << (r % 32)``
aliasing lived), random lattices/stores and random single- and multi-role
queries, each path must return exactly the brute-force per-query
authorized oracle:

  * batched     — ``store.search`` through the batched lattice engine,
  * sequential  — ``store.search`` falling back to per-query coordinated
                  search (exact engines),
  * scheduler   — ``MicroBatchScheduler`` micro-batches,
  * dynamic     — ``DynamicStore`` searches after mutations.

Runs under real hypothesis when installed, else the deterministic
``_propshim`` corpus.  The aliasing regression (a store with roles
{1, 33} leaking/crowding across the word boundary) has its own pinned
tests below — they are the kernel-parity ground truth the property
harness generalizes.
"""
import asyncio
import functools

import numpy as np
import pytest

from _propshim import given, settings, st

from repro.ann.scorescan import scorescan_factory
from repro.core import (AccessPolicy, DynamicStore, HNSWCostModel, Query,
                        build_effveda, build_vector_storage, exact_factory,
                        generate_policy, mask_words, metrics)
from repro.core.api import roles_bitmask

# role universes pinned on packed-word boundaries: the shrunk failing cases
# of the old aliasing bug live exactly at 31/32/33 and 63/64
ROLE_UNIVERSES = (8, 31, 32, 33, 63, 64, 200, 256)
DIM = 8
N_VECTORS = 360


def _fresh(n_roles: int, seed: int, scan: bool):
    """Store (ScoreScan or exact engines) over a random policy/lattice."""
    policy = generate_policy(n_vectors=N_VECTORS, n_roles=n_roles,
                             n_permissions=n_roles + 12, seed=seed)
    rng = np.random.default_rng(1000 + seed)
    vecs = rng.standard_normal((policy.n_vectors, DIM)).astype(np.float32)
    cm = HNSWCostModel(lam_threshold=60)
    res = build_effveda(policy, cm, beta=1.1, k=5)
    factory = scorescan_factory(policy) if scan else exact_factory()
    store = build_vector_storage(res, vecs, engine_factory=factory)
    return policy, vecs, store, cm


# read-only tests share cached builds; mutation tests call _fresh directly
_built = functools.lru_cache(maxsize=None)(_fresh)


def _queries(policy, vecs, seed: int, b: int = 6, k: int = 5):
    """Random single- and multi-role queries (word-boundary roles favored)."""
    rng = np.random.default_rng(2000 + seed)
    boundary = [r for r in (1, 31, 32, 33, 63, 64, 199)
                if r < policy.n_roles]
    out = []
    for i in range(b):
        x = vecs[int(rng.integers(len(vecs)))] + \
            rng.standard_normal(DIM).astype(np.float32) * 0.05
        if boundary and i % 2 == 0:
            roles = [int(rng.choice(boundary))]
        else:
            roles = [int(rng.integers(policy.n_roles))]
        if i % 3 == 2 and policy.n_roles > 1:      # multi-role union query
            roles.append(int(rng.integers(policy.n_roles)))
        out.append(Query(vector=x, roles=tuple(set(roles)), k=k))
    return out


def _oracle_ids(policy, vecs, q: Query):
    mask = np.zeros(len(vecs), dtype=bool)
    ids = policy.d_of_roleset(q.roles)
    mask[ids] = True
    return [i for _, i in metrics.brute_force_topk(vecs, mask, q.vector,
                                                   q.k)]


def _assert_matches_oracle(policy, vecs, queries, results):
    for q, res in zip(queries, results):
        want = _oracle_ids(policy, vecs, q)
        got = [i for _, i in res]
        assert got == want[:len(got)] and len(got) == len(want), (
            q.roles, got, want)


# ------------------------------------------------------------ property tests
@settings(max_examples=12, deadline=None)
@given(n_roles=st.sampled_from(ROLE_UNIVERSES), seed=st.integers(0, 3))
def test_batched_path_matches_authorized_oracle(n_roles, seed):
    policy, vecs, store, _ = _built(n_roles, seed, scan=True)
    queries = _queries(policy, vecs, seed)
    results = store.search(queries)
    assert all(r.path.startswith("batched") for r in results)
    _assert_matches_oracle(policy, vecs, queries, results)


@settings(max_examples=12, deadline=None)
@given(n_roles=st.sampled_from(ROLE_UNIVERSES), seed=st.integers(0, 3))
def test_sequential_path_matches_authorized_oracle(n_roles, seed):
    policy, vecs, store, _ = _built(n_roles, seed, scan=False)
    queries = _queries(policy, vecs, seed)
    results = store.search(queries)
    # exact engines take the per-query path; a lattice with no node (every
    # block a leftover, e.g. 256 roles at seed 0) is batch-capable by design
    want = "sequential" if store.engines else "batched"
    assert all(r.path == want for r in results)
    _assert_matches_oracle(policy, vecs, queries, results)


@settings(max_examples=8, deadline=None)
@given(n_roles=st.sampled_from(ROLE_UNIVERSES), seed=st.integers(0, 2))
def test_scheduler_path_matches_authorized_oracle(n_roles, seed):
    from repro.launch.scheduler import MicroBatchScheduler
    policy, vecs, store, _ = _built(n_roles, seed, scan=True)
    queries = _queries(policy, vecs, seed)

    async def run():
        sched = MicroBatchScheduler(store, max_batch=4, max_wait_ms=1.0)
        try:
            futs = [sched.submit(q) for q in queries]
            return await asyncio.gather(*futs)
        finally:
            await sched.close()

    results = asyncio.run(run())
    _assert_matches_oracle(policy, vecs, queries, results)


@settings(max_examples=8, deadline=None)
@given(n_roles=st.sampled_from(ROLE_UNIVERSES), seed=st.integers(0, 2))
def test_dynamic_path_matches_authorized_oracle(n_roles, seed):
    """Insert / delete / grant / revoke, then every role's searches must
    match an exact rescan of the mutated state — auth mask words included
    (the rebuilds carry (W,) rows past 32 roles)."""
    policy, vecs, store, cm = _fresh(n_roles, seed, scan=True)
    dyn = DynamicStore(store, cm)
    rng = np.random.default_rng(3000 + seed)
    hi = policy.n_roles - 1
    dyn.insert(rng.standard_normal(DIM).astype(np.float32),
               frozenset({hi}))                      # top word's last role
    dyn.delete(int(policy.d_of_role(0)[0]))
    alive = [v for v in range(N_VECTORS) if v not in dyn.tombstones]
    dyn.grant(int(alive[1]), hi)
    for i in range(4):
        r = int(rng.integers(policy.n_roles)) if i % 2 else hi
        x = rng.standard_normal(DIM).astype(np.float32)
        mask = dyn.store.authorized_mask(r).copy()
        for t in dyn.tombstones:
            mask[t] = False
        want = [v for _, v in metrics.brute_force_topk(
            dyn.store.data, mask, x, 5)]
        got = [v for _, v in dyn.search(x, r, k=5)]
        assert got == want[:len(got)] and len(got) == len(want), r


# ------------------------------------------------ pinned regression tests
def _two_word_policy():
    """Roles {1, 33}: the minimal universe where `1 << (r % 32)` made role
    33 alias role 1 (same bit, different word now)."""
    rng = np.random.default_rng(9)
    n = 240
    assign = rng.integers(0, 3, size=n)
    members = tuple(np.flatnonzero(assign == b).astype(np.int64)
                    for b in range(3))
    return AccessPolicy(
        n_roles=34,
        block_roles=(frozenset({1}), frozenset({33}), frozenset({1, 33})),
        block_members=members)


def test_roles_bitmask_aliasing_is_a_hard_error():
    """The legacy single-word helpers must refuse roles past the word —
    never silently wrap (role 33 used to land on bit 1)."""
    with pytest.raises(ValueError):
        roles_bitmask((33,))
    with pytest.raises(ValueError):
        roles_bitmask((1, 33))
    with pytest.raises(ValueError):
        _two_word_policy().role_bitmask(max_roles=32)


def test_role_33_never_served_to_role_1():
    """Regression (ISSUE satellite): a store with roles {1, 33} must never
    return role-33-only vectors to role 1.  Under the old modulo the two
    roles shared in-kernel bit 1, so role-33-only vectors could crowd
    role-1 results out of the kernel top-k (and leak outright through
    mask-level calls).  Fixed behavior — exact word masks — is the
    kernel-parity ground truth."""
    policy = _two_word_policy()
    rng = np.random.default_rng(10)
    vecs = rng.standard_normal((policy.n_vectors, DIM)).astype(np.float32)
    cm = HNSWCostModel(lam_threshold=40)
    res = build_effveda(policy, cm, beta=1.2, k=5)
    store = build_vector_storage(res, vecs,
                                 engine_factory=scorescan_factory(policy))
    assert store.mask_width == 2
    only_33 = set(int(v) for v in policy.block_members[1])
    for seed in range(6):
        x = vecs[seed * 7] + 0.01
        for roles in ((1,), (33,), (1, 33)):
            q = Query(vector=x, roles=roles, k=8)
            res_b = store.search(q)[0]
            got = [i for _, i in res_b]
            if roles == (1,):
                assert not (set(got) & only_33), "role-33 leak to role 1"
            want = _oracle_ids(policy, vecs, q)
            assert got == want[:len(got)] and len(got) == len(want)
    # engine-level ground truth: the kernel's word mask for role 1 admits
    # no role-33-only vector in ANY node shard
    mask1 = store.kernel_role_mask((1,))
    for eng in store.engines.values():
        for _, vid in eng.search_masked(vecs[3], len(eng), mask1):
            assert vid not in only_33


def test_n200_store_acceptance():
    """ISSUE acceptance: n_roles=200 — batched and sequential paths return
    exactly the per-query authorized oracle, and the packed leftover shard
    no longer refuses n_roles > 32."""
    n_roles, seed = 200, 1
    policy, vecs, store, _ = _built(n_roles, seed, scan=True)
    assert store.mask_width == mask_words(200) == 7
    shard = store.pack_leftover_shard()
    if sum(len(v) for v in store.leftover_vectors.values()):
        assert shard is not None and shard.mask_width == 7
    queries = _queries(policy, vecs, seed, b=8)
    batched = store.search(queries)
    assert all(r.path.startswith("batched") for r in batched)
    _assert_matches_oracle(policy, vecs, queries, batched)
    _, _, seq_store, _ = _built(n_roles, seed, scan=False)
    seq = seq_store.search(queries)
    assert all(r.path == "sequential" for r in seq)
    _assert_matches_oracle(policy, vecs, queries, seq)


def test_n64_many_role_smoke():
    """Fast many-role smoke (also run by scripts/ci_check.sh): a 64-role
    store (W=2) serves exact authorized results through the batched path."""
    policy, vecs, store, _ = _built(64, 0, scan=True)
    assert store.mask_width == 2
    queries = _queries(policy, vecs, 0, b=4)
    results = store.search(queries)
    assert all(r.path.startswith("batched") for r in results)
    _assert_matches_oracle(policy, vecs, queries, results)


def test_hnsw_reinsert_refreshes_auth_words():
    """Regression (code review): re-inserting an already-linked id (a
    tombstoned vector re-granted under a new role set) keeps the graph row
    but must refresh its auth words — stale words would keep serving the
    old role set through search_masked."""
    from repro.ann.hnsw import HNSWIndex
    rng = np.random.default_rng(30)
    data = rng.standard_normal((50, DIM)).astype(np.float32)
    words = np.zeros((50, 2), np.uint32)
    words[:, 0] = 1                                   # everyone role 0
    idx = HNSWIndex(data, M=4, efc=16, auth_bits=words)
    idx.tombstone(7)
    new_row = np.array([0, 2], np.uint32)             # now role-33-only
    idx.insert(7, data[7], auth_bits=new_row)         # early-return path
    assert (idx.auth_bits[7] == new_row).all()
    mask33 = np.array([0, 2], np.uint32)
    got33 = [v for _, v in idx.search_masked(data[7], 5, mask33)]
    assert got33 == [7]                               # visible to role 33
    mask0 = np.array([1, 0], np.uint32)
    got0 = [v for _, v in idx.search_masked(data[7], 50, mask0)]
    assert 7 not in got0                              # and only role 33


def test_warm_batch_shapes_uses_store_mask_width():
    """The serving warm-up must trace the store's real (B, W) mask operands
    — a single-word warm-up on a W=2 store would compile dead signatures
    and leave every real launch cold."""
    from repro.launch.serve import warm_batch_shapes
    _, _, store, _ = _built(64, 0, scan=True)
    assert store.mask_width == 2
    assert store.role_mask_rows([(0,), (33,)]).shape == (2, 2)
    n_engines = sum(1 for e in store.engines.values() if len(e))
    # sizes 1 and 8 pad to the same bq=8 bucket: one warm call per engine,
    # not two (an interpret-mode warm call is a real O(N) scan)
    calls = warm_batch_shapes(store, sizes=(1, 8), k=5)
    assert calls == n_engines > 0
    assert warm_batch_shapes(store, sizes=(8, 16), k=5) == 2 * n_engines


# --------------------------------------------- churn + compaction property
@settings(max_examples=6, deadline=None)
@given(n_roles=st.sampled_from((8, 40)), seed=st.integers(0, 2))
def test_sustained_churn_with_compaction_matches_oracle(n_roles, seed):
    """ISSUE 6 satellite: interleave insert/delete/grant/revoke with
    single- and multi-role searches (W=1 at 8 roles, W=2 at 40), check
    every answer against the brute-force authorized oracle, and assert a
    maintain() cycle — folds + tombstone purges — never changes answers.
    The multi-role combination straddles the 32-role word boundary."""
    from repro.core import CompactionConfig, LatticeCompactor

    policy, vecs, store, cm = _fresh(n_roles, seed, scan=True)
    dyn = DynamicStore(store, cm)
    comp = LatticeCompactor(dyn, CompactionConfig(
        tombstone_purge_threshold=6, leftover_fold_threshold=25))
    rng = np.random.default_rng(5000 + 10 * seed + n_roles)
    hi = min(n_roles - 1, 33)                # crosses the word boundary
    combo = frozenset({0, hi})

    def oracle(x, roles, k):
        mask = dyn.store.authorized_mask_multi(roles).copy()
        for t in dyn.tombstones:
            mask[t] = False
        return [v for _, v in metrics.brute_force_topk(dyn.store.data,
                                                       mask, x, k)]

    def alive():
        return [v for v in range(len(dyn.store.data))
                if v not in dyn.tombstones]

    for step in range(40):
        op = step % 4
        if op == 0:
            dyn.insert(rng.standard_normal(DIM).astype(np.float32), combo)
        elif op == 1:
            tau = frozenset({int(rng.integers(n_roles))})
            dyn.insert(rng.standard_normal(DIM).astype(np.float32), tau)
        elif op == 2:
            dyn.delete(int(rng.choice(alive())))
        else:
            vid = int(rng.choice(alive()))
            r = int(rng.integers(n_roles))
            tau = dyn.block_roles[dyn.vec_block[vid]]
            if r in tau and len(tau) > 1:
                dyn.revoke(vid, r)
            else:
                dyn.grant(vid, r)
        if step % 10 == 9:
            queries = [(rng.standard_normal(DIM).astype(np.float32),
                        (int(rng.integers(n_roles)),) if i % 2
                        else (0, hi))
                       for i in range(4)]
            pre = [[v for _, v in dyn.search(x, roles=roles, k=5)]
                   for x, roles in queries]
            for (x, roles), got in zip(queries, pre):
                want = oracle(x, roles, 5)
                assert got == want[:len(got)], (roles, got, want)
                assert len(got) == len(want)
            comp.maintain(budget_s=2.0)
            post = [[v for _, v in dyn.search(x, roles=roles, k=5)]
                    for x, roles in queries]
            assert post == pre, "compaction changed answers"
    assert len(dyn.tombstones) <= 6          # purge threshold is the bound


@settings(max_examples=4, deadline=None)
@given(n_roles=st.sampled_from((8, 40)), seed=st.integers(0, 2))
def test_drift_reoptimization_under_rotating_popularity(n_roles, seed):
    """Drift-driven re-optimization interleaved with churn (W=1 at 8
    roles, W=2 at 40): role popularity rotates each batch — the popular
    role's blocks take an insert burst while the previous favorite is
    culled — and maintain() between batches runs the split/remerge/drop
    pass over whatever nodes crossed the drift slack.  Every answer
    matches the brute-force authorized oracle, a maintain() cycle never
    changes answers, SA is monotone non-increasing across maintain()
    calls, and the flagged set converges to empty once churn stops."""
    from repro.core import CompactionConfig, LatticeCompactor

    policy, vecs, store, cm = _fresh(n_roles, seed, scan=True)
    dyn = DynamicStore(store, cm)
    comp = LatticeCompactor(dyn, CompactionConfig(
        tombstone_purge_threshold=6, leftover_fold_threshold=25))
    rng = np.random.default_rng(9000 + 10 * seed + n_roles)
    hi = min(n_roles - 1, 33)                # crosses the word boundary

    def oracle(x, roles, k):
        mask = dyn.store.authorized_mask_multi(roles).copy()
        for t in dyn.tombstones:
            mask[t] = False
        return [v for _, v in metrics.brute_force_topk(dyn.store.data,
                                                       mask, x, k)]

    inserted = {}                            # popular role -> its vids
    for rnd in range(4):
        pop = rnd % min(n_roles, 4)          # rotating popularity
        vids = inserted.setdefault(pop, [])
        for i in range(24):                  # burst toward the favorite
            tau = frozenset({pop}) if i % 2 else frozenset({pop, hi})
            vids.append(dyn.insert(
                rng.standard_normal(DIM).astype(np.float32), tau))
        prev = (rnd - 1) % min(n_roles, 4)
        stale = [v for v in inserted.get(prev, ())
                 if v not in dyn.tombstones]
        for v in stale[:16]:                 # cull last round's favorite
            dyn.delete(v)
        queries = [(rng.standard_normal(DIM).astype(np.float32),
                    (int(rng.integers(n_roles)),) if i % 2 else (pop, hi))
                   for i in range(4)]
        pre = [[v for _, v in dyn.search(x, roles=roles, k=5)]
               for x, roles in queries]
        for (x, roles), got in zip(queries, pre):
            want = oracle(x, roles, 5)
            assert got == want[:len(got)], (roles, got, want)
            assert len(got) == len(want)
        sa_before = dyn.store.sa()
        comp.maintain(budget_s=2.0)
        assert dyn.store.sa() <= sa_before + 1e-9, \
            "maintain() raised storage amplification"
        post = [[v for _, v in dyn.search(x, roles=roles, k=5)]
                for x, roles in queries]
        assert post == pre, "drift re-optimization changed answers"
    for _ in range(3):                       # quiescence: flags drain
        if not dyn.needs_reoptimization():
            break
        comp.maintain(budget_s=2.0)
    assert dyn.needs_reoptimization() == []
    x = rng.standard_normal(DIM).astype(np.float32)
    for roles in [(0,), (hi,), (0, hi)]:
        got = [v for _, v in dyn.search(x, roles=roles, k=5)]
        want = oracle(x, roles, 5)
        assert got == want[:len(got)] and len(got) == len(want)


# ------------------------------------------------- churn + answer cache
@settings(max_examples=6, deadline=None)
@given(n_roles=st.sampled_from((8, 40)), seed=st.integers(0, 2))
def test_churn_with_answer_cache_never_serves_stale(n_roles, seed):
    """ISSUE satellite: the auth-aware answer cache under sustained
    insert/delete/grant/revoke churn (plus compaction cycles, which clear
    it on purge).  A fixed query pool is re-asked every round — twice, so
    repeats are served from the cache — and every answer, cached or fresh,
    must match the brute-force authorized oracle of the *current* state.
    A stale hit after a revoke would surface a vector the role set just
    lost: an access-control leak.  ``hits > 0`` keeps the test
    non-vacuous."""
    from repro.core import (AnswerCache, CompactionConfig, LatticeCompactor)

    policy, vecs, store, cm = _fresh(n_roles, seed, scan=True)
    dyn = DynamicStore(store, cm)
    cache = AnswerCache(capacity=256)
    dyn.attach_cache(cache)
    comp = LatticeCompactor(dyn, CompactionConfig(
        tombstone_purge_threshold=6, leftover_fold_threshold=25))
    rng = np.random.default_rng(7000 + 10 * seed + n_roles)
    hi = min(n_roles - 1, 33)                # crosses the word boundary
    combo = frozenset({0, hi})
    pool = [(rng.standard_normal(DIM).astype(np.float32),
             (int(rng.integers(n_roles)),) if i % 2 else (0, hi))
            for i in range(6)]

    def oracle(x, roles, k):
        mask = dyn.store.authorized_mask_multi(roles).copy()
        for t in dyn.tombstones:
            mask[t] = False
        return [v for _, v in metrics.brute_force_topk(dyn.store.data,
                                                       mask, x, k)]

    def alive():
        return [v for v in range(len(dyn.store.data))
                if v not in dyn.tombstones]

    for step in range(40):
        op = step % 4
        if op == 0:
            dyn.insert(rng.standard_normal(DIM).astype(np.float32), combo)
        elif op == 1:
            tau = frozenset({int(rng.integers(n_roles))})
            dyn.insert(rng.standard_normal(DIM).astype(np.float32), tau)
        elif op == 2:
            dyn.delete(int(rng.choice(alive())))
        else:
            vid = int(rng.choice(alive()))
            r = int(rng.integers(n_roles))
            tau = dyn.block_roles[dyn.vec_block[vid]]
            if r in tau and len(tau) > 1:
                dyn.revoke(vid, r)
            else:
                dyn.grant(vid, r)
        if step % 5 == 4:
            for x, roles in pool:
                want = oracle(x, roles, 5)
                for _ in range(2):           # second ask rides the cache
                    got = [v for _, v in dyn.search(x, roles=roles, k=5)]
                    assert got == want[:len(got)], (roles, got, want)
                    assert len(got) == len(want)
        if step % 10 == 9:
            comp.maintain(budget_s=2.0)
    assert cache.stats.hits > 0              # the cache actually served
