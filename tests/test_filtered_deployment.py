"""The benchmark's filtered deployment (``marco768-label12``) at a CPU size:
the paper's section 7.1 roles at the ``paper-like`` skews (32 roles, 120
permission sets), one label field of 12 uniform values on every row, an
equality filter on every query, 20% two-role unions.

Each query is served through ``MicroBatchScheduler`` -> ``VectorStore.search``
on the two leftover paths of the batched engine (the packed shard, which
filters in the kernel, and the per-block scans, which use the host clause
masks) and held to a float64 brute force over the rows the query's roles
may read that carry its label.  The plan's spans for the auth and
predicate planes (``search.authmask``, ``search.predicate``) sit under
``search.plan``, its counters count what the flush did, and the answers
are the same with the recorder's spans as without them.
"""
import asyncio
import functools
import time

import numpy as np
import pytest

from repro import obs
from repro.ann.scorescan import scorescan_factory
from repro.core import (HNSWCostModel, Query, build_effveda,
                        build_vector_storage, generate_policy, metrics)
from repro.core.predicate import PredicateSchema
from repro.launch.scheduler import MicroBatchScheduler

pytestmark = pytest.mark.filtered

N_VECTORS = 4000
DIM = 16
LABELS = 12
K = 10
N_QUERIES = 24
MAX_BATCH = 8

# leftover path -> the scheduler's min_packed_batch that selects it
PATHS = {"batched+packed": 1, "batched": 10 ** 9}


@functools.lru_cache(maxsize=None)
def _deployment():
    policy = generate_policy(n_vectors=N_VECTORS, n_roles=32,
                             n_permissions=120, block_zipf=(1.0, 2.0),
                             perm_zipf=(2.0, 1.5), max_roles_per_perm=5,
                             seed=1)
    rng = np.random.default_rng(16)
    centers = rng.standard_normal((8, DIM)).astype(np.float32) * 4.0
    vecs = (centers[rng.integers(8, size=N_VECTORS)]
            + rng.standard_normal((N_VECTORS, DIM))).astype(np.float32)
    labels = rng.integers(LABELS, size=N_VECTORS)
    schema = PredicateSchema.make(
        tags={"tenant": tuple(str(v) for v in range(LABELS))})
    attrs = schema.encode_rows([{"tenant": str(v)} for v in labels])
    built = build_effveda(policy, HNSWCostModel(lam_threshold=30),
                          beta=1.1, k=K)
    store = build_vector_storage(
        built, vecs, engine_factory=scorescan_factory(policy,
                                                      attr_words=attrs),
        pack_leftovers=True, pred_schema=schema, attr_words=attrs)
    assert store.engines and store.leftover_ids
    assert store.leftover_shard is not None and store.pred_width == 1
    queries = []
    for _ in range(N_QUERIES):
        r = int(rng.integers(policy.n_roles))
        roles = (r,)
        if rng.random() < 0.2:
            roles += (int((r + 1 + rng.integers(policy.n_roles - 1))
                          % policy.n_roles),)
        own = policy.d_of_role(r)
        x = vecs[own[rng.integers(len(own))]] \
            + 0.1 * rng.standard_normal(DIM).astype(np.float32)
        where = (("has", "tenant", str(int(rng.integers(LABELS)))),)
        queries.append(Query(vector=x.astype(np.float32), roles=roles, k=K,
                             where=where))
    return policy, vecs, labels, store, queries


def _serve(store, queries, min_packed_batch):
    """Serve ``queries`` through the scheduler; returns the results and
    the batches the scheduler's flushes searched, in order."""
    batches = []

    def search(st, qs):
        batches.append(list(qs))
        return st.search(qs, min_packed_batch=min_packed_batch)

    async def main():
        sched = MicroBatchScheduler(store, max_batch=MAX_BATCH,
                                    max_wait_ms=1.0, search_fn=search)
        try:
            return await asyncio.gather(*[sched.submit(q) for q in queries])
        finally:
            await sched.close()
    return asyncio.run(main()), batches


@functools.lru_cache(maxsize=None)
def _served(path):
    _, _, _, store, queries = _deployment()
    t = time.perf_counter()
    results, batches = _serve(store, queries, PATHS[path])
    roots = sorted((r for r in obs.roots(since=t)
                    if r.span.name == "serve.flush"),
                   key=lambda r: r.span.t0)
    spans = obs.spans(since=t)
    return results, batches, roots, spans


@pytest.mark.parametrize("path", sorted(PATHS))
def test_served_answers_match_the_filtered_brute_force(path):
    policy, vecs, labels, _, queries = _deployment()
    results, _, _, _ = _served(path)
    vecs64 = vecs.astype(np.float64)
    for q, res in zip(queries, results):
        assert res.path == path
        ok = np.zeros(len(vecs), bool)
        ok[policy.d_of_roleset(q.roles)] = True
        ok &= labels == int(q.where[0][2])
        want = [d for d, _ in metrics.brute_force_topk(vecs64, ok, q.vector,
                                                       q.k)]
        got = np.asarray([i for _, i in res.hits], np.int64)
        assert len(got) == min(q.k, int(ok.sum())) == len(want)
        assert len(set(got.tolist())) == len(got)
        # every hit readable by one of the roles and carrying the label
        assert ok[got].all(), (q.roles, q.where, got)
        # the same ids up to distance ties: the same distances, in order
        gd = vecs64[got] - q.vector
        np.testing.assert_allclose(np.einsum("nd,nd->n", gd, gd), want,
                                   rtol=1e-5, atol=1e-5)
        served = np.asarray([dd for dd, _ in res.hits])
        assert (np.diff(served) >= 0).all()


@pytest.mark.parametrize("path", sorted(PATHS))
def test_plan_spans_and_counters_of_each_flush(path):
    _, batches, roots, spans = _served(path)
    assert len(roots) == len(batches) > 1
    by_id = {s.id: s for s in spans}
    for name in ("search.authmask", "search.predicate"):
        found = [s for s in spans if s.name == name]
        assert len(found) == len(batches)
        for s in found:
            assert by_id[s.parent].name == "search.plan"
    for root, batch in zip(roots, batches):
        c = root.counts
        assert c["clauses"] == len({q.where for q in batch})
        assert c["role_sets"] == len({q.roles for q in batch})
        # every query is filtered: every launch carries require/forbid rows
        assert 0 < c["filtered_launches"] <= c["launches"]
        assert c["filtered_launches"] == c["launches"]
        assert root.seconds["search.predicate"] > 0
        assert root.seconds["search.authmask"] > 0


class _Silent:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


@pytest.mark.parametrize("path", sorted(PATHS))
def test_answers_do_not_depend_on_the_recorder(path, monkeypatch):
    results, batches, _, _ = _served(path)
    monkeypatch.setattr(obs, "span", lambda name, **attrs: _Silent())
    monkeypatch.setattr(obs, "count", lambda name, n=1: None)
    _, _, _, store, queries = _deployment()
    served = {id(q): r for q, r in zip(queries, results)}
    for batch in batches:
        for q, r in zip(batch, store.search(
                batch, min_packed_batch=PATHS[path])):
            want = served[id(q)]
            assert (r.path, r.hits, r.stats) == (want.path, want.hits,
                                                 want.stats)
