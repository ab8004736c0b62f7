"""The span-and-counter recorder (``repro.obs``): nesting and roots, the
spans of a served flush and their per-flush sums, the launches of a
sharded store in their flush's tree, the host-to-device byte counter and
the node operands that stay on the device, compile counts on the step that
compiled, and the bounds of what the recorder holds."""
import asyncio
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.ann.scorescan import scorescan_factory
from repro.core import (HNSWCostModel, Query, build_effveda,
                        build_vector_storage, generate_policy, shard_store)
from repro.kernels.l2_topk import l2_topk, prepare_node
from repro.launch.mesh import DeviceMesh
from repro.launch.scheduler import MicroBatchScheduler, serve_requests
from repro.launch.serve import warm_batch_shapes


def _store():
    policy = generate_policy(n_vectors=1200, n_roles=6, n_permissions=16,
                             seed=4)
    built = build_effveda(policy, HNSWCostModel(lam_threshold=100),
                          beta=1.1, k=10)
    vecs = np.random.default_rng(1).standard_normal(
        (policy.n_vectors, 16)).astype(np.float32)
    return build_vector_storage(built, vecs,
                                engine_factory=scorescan_factory(policy),
                                pack_leftovers=True)


@pytest.fixture(scope="module")
def store():
    return _store()


def _queries(store, n, seed):
    rng = np.random.default_rng(seed)
    vecs = store.data[rng.integers(len(store.data), size=n)] + 0.01
    return [Query(vector=vecs[i].astype(np.float32),
                  roles=(int(rng.integers(store.policy.n_roles)),), k=5)
            for i in range(n)]


def _serve(store, queries):
    async def main():
        sched = MicroBatchScheduler(store, max_batch=8, max_wait_ms=1.0)
        try:
            return await serve_requests(sched, queries)
        finally:
            await sched.close()
    return asyncio.run(main())


def _one_launch(n=300, b=5, d=16, seed=0, **kw):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, d)).astype(np.float32)
    db = rng.standard_normal((n, d)).astype(np.float32)
    auth = rng.integers(0, 2 ** 8, size=n).astype(np.uint32)
    masks = np.full(b, 0xFF, np.uint32)
    bounds = np.full(b, np.inf, np.float32)
    return q, db, auth, masks, bounds


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_nesting_parents_and_roots_on_one_thread():
    t = time.perf_counter()
    with obs.span("outer", a=1) as outer:
        with obs.span("mid") as mid:
            with obs.span("leaf") as leaf:
                obs.count("things", 3)
            obs.count("things", 2)
        with obs.span("mid2") as mid2:
            pass
    got = obs.spans(since=t)
    assert [s.name for s in got] == ["leaf", "mid", "mid2", "outer"]
    assert outer.parent is None and outer.root == outer.id
    assert mid.parent == outer.id and mid2.parent == outer.id
    assert leaf.parent == mid.id
    assert {s.root for s in (mid, leaf, mid2)} == {outer.id}
    # a counter lands on the innermost open span only
    assert leaf.attrs["things"] == 3 and mid.attrs["things"] == 2
    assert "things" not in outer.attrs and outer.attrs["a"] == 1
    assert outer.t0 <= mid.t0 <= leaf.t0 <= leaf.t1 <= mid.t1 <= outer.t1
    assert obs.counters()["things"] >= 5
    tree = obs.tree(outer, [outer, mid, leaf, mid2])
    assert [c["name"] for c in tree["children"]] == ["mid", "mid2"]
    assert tree["children"][0]["children"][0]["name"] == "leaf"


def test_every_span_of_a_flush_carries_its_flush_root(store):
    t = time.perf_counter()
    results = _serve(store, _queries(store, 24, 3))
    assert len(results) == 24
    got = obs.spans(since=t)
    flushes = _named(got, "serve.flush")
    assert flushes
    roots = {f.id for f in flushes}
    inner = [s for s in got
             if s.name.split(".")[0] in ("search", "scan", "l2_topk")]
    assert {"search.plan", "search.leftovers", "search.wave",
            "search.bound", "search.merge", "scan.launch",
            "l2_topk.prep", "l2_topk.dispatch",
            "scan.readback"} <= {s.name for s in inner}
    by_id = {f.id: f for f in flushes}
    for s in inner:
        assert s.root in roots, s
        f = by_id[s.root]
        assert f.t0 <= s.t0 <= s.t1 <= f.t1
    for f in flushes:
        assert {"flush", "batch", "reason", "handoff_ms"} <= set(f.attrs)
        assert f.attrs["handoff_ms"] >= 0
    assert len(_named(got, "serve.resolve")) == len(flushes)
    # each launch is split into prep, dispatch and read-back
    for launch in _named(inner, "scan.launch"):
        kids = {s.name for s in inner if s.parent == launch.id}
        assert kids == {"l2_topk.prep", "l2_topk.dispatch", "scan.readback"}
    # each flush's sums: the seconds of its spans by name, its counters
    by_root = {r.span.id: r for r in obs.roots(since=t)}
    for f in flushes:
        r = by_root[f.id]
        mine = [s for s in inner if s.root == f.id]
        for name in ("l2_topk.prep", "scan.readback", "search.merge"):
            assert r.seconds[name] == pytest.approx(
                sum(s.seconds for s in mine if s.name == name))
        assert r.counts["h2d_bytes"] == sum(
            s.attrs.get("h2d_bytes", 0) for s in mine) > 0
        assert r.counts["launches"] == len(_named(mine, "scan.launch"))
    trees = obs.slowest(2, since=t)
    assert len(trees) == 2 and trees[0]["ms"] >= trees[1]["ms"]
    assert trees[0]["name"] == "serve.flush" and trees[0]["children"]


@pytest.mark.parametrize("node", ["host", "device", "resident"],
                         ids=["host-db", "device-db", "resident"])
def test_h2d_bytes_counts_host_operands_only(node):
    q, db, auth, masks, bounds = _one_launch()
    host = q.nbytes + masks.nbytes + bounds.nbytes
    if node == "resident":
        t = time.perf_counter()
        db, auth = prepare_node(db, auth), None
        # the rows and auth words cross once, in their own span
        (up,) = _named(obs.spans(since=t), "scan.upload")
        assert up.attrs["upload_bytes"] == 4 * 300 * (16 + 1)
    elif node == "device":
        db = jnp.asarray(db)
        host += auth.nbytes
    else:
        host += db.nbytes + auth.nbytes
    l2_topk(q, db, auth, masks, 4, bound=bounds)      # compile outside
    t = time.perf_counter()
    before = obs.counters().get("h2d_bytes", 0)
    l2_topk(q, db, auth, masks, 4, bound=bounds)
    (prep,) = _named(obs.spans(since=t), "l2_topk.prep")
    assert prep.attrs["h2d_bytes"] == host
    assert obs.counters()["h2d_bytes"] - before == host
    assert prep.attrs["launches"] == 1
    assert prep.attrs.get("resident_launches", 0) == (node == "resident")
    assert prep.attrs["rows_scanned"] == 300
    # 300 rows padded to 512, 5 query rows padded to 8
    assert prep.attrs["rows_padded"] == 212 + 3


def test_served_flushes_launch_resident_node_operands(store):
    warm_batch_shapes(store, sizes=(8,))      # every engine uploads here
    t = time.perf_counter()
    results = _serve(store, _queries(store, 24, 12))
    assert len(results) == 24
    got = obs.spans(since=t)
    assert not _named(got, "scan.upload")
    roots = [r for r in obs.roots(since=t) if r.span.name == "serve.flush"]
    assert roots
    for r in roots:
        assert r.counts["resident_launches"] == r.counts["launches"] > 0
        assert "upload_bytes" not in r.counts
    # a launch sends its queries, role masks and bounds, no node row
    d = store.data.shape[1]
    for launch in _named(got, "scan.launch"):
        (prep,) = [s for s in got
                   if s.parent == launch.id and s.name == "l2_topk.prep"]
        b = launch.attrs["b"]
        assert prep.attrs["h2d_bytes"] in (4 * b * (d + 1), 4 * b * (d + 2))


def test_first_call_at_a_new_shape_compiles_on_dispatch():
    # a shape no other test launches: 7 queries over 333 rows at k 3
    q, db, auth, masks, bounds = _one_launch(n=333, b=7, d=24, seed=5)
    spans = []
    for _ in range(2):
        t = time.perf_counter()
        l2_topk(q, db, auth, masks, 3, bound=bounds)
        spans.append(_named(obs.spans(since=t), "l2_topk.dispatch")[0])
    assert spans[0].attrs.get("compiles", 0) >= 1
    assert spans[0].attrs.get("compile_s", 0) > 0
    assert spans[1].attrs.get("compiles", 0) == 0


def test_sharded_launches_stay_in_their_flush_tree():
    sharded = shard_store(_store(), DeviceMesh.host(2))
    try:
        t = time.perf_counter()
        results = _serve(sharded, _queries(sharded.store, 16, 7))
    finally:
        sharded.close()
    assert len(results) == 16
    got = obs.spans(since=t)
    flushes = {s.id: s for s in _named(got, "serve.flush")}
    by_id = {s.id: s for s in got}
    launches = _named(got, "scan.launch")
    assert {s.attrs["slot"] for s in launches} == {0, 1}
    for launch in launches:
        # opened on a slot's thread, under the search span that submitted it
        assert launch.root in flushes
        assert by_id[launch.parent].name.startswith("search.")
        assert launch.thread != flushes[launch.root].thread
        kids = {s.name for s in got if s.parent == launch.id}
        assert kids == {"l2_topk.prep", "l2_topk.dispatch", "scan.readback"}
    # host seconds inside launches: the slots' scan.launch spans summed
    busy = sum(sharded.device_busy_s)
    assert busy >= sum(s.seconds for s in launches) > 0


def test_root_ring_is_bounded_and_counts_what_it_drops():
    t = time.perf_counter()
    dropped = obs.dropped()
    for _ in range(obs.ROOTS + 10):
        with obs.span("fill"):
            pass
    assert obs.dropped() - dropped >= 10
    # roots that began after t were let go: a reader gets nothing
    assert obs.roots(since=t) is None and obs.spans(since=t) is None
    t2 = time.perf_counter()
    with obs.span("after"):
        pass
    assert [r.span.name for r in obs.roots(since=t2)] == ["after"]
    assert [s.name for s in obs.spans(since=t2)] == ["after"]


def test_a_root_keeps_its_sums_when_its_tree_is_let_go():
    t = time.perf_counter()
    with obs.span("big") as big:
        for _ in range(obs.RING + 10):
            with obs.span("leaf") as leaf:
                obs.count("things", 2)
    # the tree outgrew the ring: its spans are gone, its sums are not
    assert obs.spans(since=t) is None
    assert obs.held_from() >= big.t1
    (root,) = obs.roots(since=t)
    assert root.span is big and root.spans is None
    assert root.counts["things"] == 2 * (obs.RING + 10)
    assert 0 < root.seconds["leaf"] <= root.seconds["big"]
    assert leaf.root == big.id
    assert all(tr["name"] != "big" for tr in obs.slowest(5, root="big"))
    t2 = time.perf_counter()
    with obs.span("after"):
        pass
    assert [s.name for s in obs.spans(since=t2)] == ["after"]
