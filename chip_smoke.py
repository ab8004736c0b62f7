"""Smoke run of the authorized vector store on a TPU, checked end to end.

Run from the root of a checkout, on a machine whose JAX backend is a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded phase only, four chips

The one-chip run drives the served retrieval path once, through the entry
points a user calls, at the deployment size of ROADMAP deployment 1:

1. main store: a SIFT1M-shaped corpus (1,000,000 x 128 float32 under L2,
   the shape of ANN-benchmarks ``sift-128-euclidean``) generated from
   ``--seed``, 32 roles, 120 permission sets and the benchmark's
   ``paper-like`` block/permission Zipf skew; EffVEDA lattice at beta 1.1,
   ScoreScan node engines and the packed leftover shard;
2. compiled kernel: one engine's ``l2_topk`` launch is lowered through the
   kernel wrapper and must hold the Mosaic kernel (``tpu_custom_call``);
3. warm-up: ``warm_batch_shapes`` compiles the kernel shapes serving hits
   (set-up time, printed with its compile count and seconds);
4. batches through ``VectorStore.search``: single-role and multi-role union
   queries, k in {1, 10, 100} and a mixed-k batch;
5. served requests: a few hundred queries through ``MicroBatchScheduler``
   (``serve_requests``, ``max_batch=64``);
6. wide store: 100,000 x 128 with 256 roles (W=8 mask words) and a 2-word
   predicate plane (P=2), answering filtered and unfiltered batches.

``--chips 4`` builds the main store, places it across four chips with
``store.sharded(DeviceMesh.host(4))``, checks that every device shard and
every launch's output lies on its own chip, and compares the sharded
answers with the one-chip ``VectorStore.search`` answers (identical hits
and distances) and with the oracle.  It runs no other phase.

Every answer is held to a float64 host brute force over the authorized (and
predicate-passing) rows: the ids must match position by position, except
where the exact distances tie within ``tol(d_k) = TIE_ATOL + TIE_RTOL * d_k``
(d_k the oracle's k-th distance); every returned distance must lie within
the same tolerance of its exact value; every hit must be authorized; and no
answer may take the per-query ``sequential`` path.  A mismatch, or an
exception carried back by a scheduler future, exits nonzero.

Without a TPU the script exits nonzero and prints no result.  The times it
prints are smoke timings of one run, not benchmark numbers.  The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Tie tolerance on squared L2 distances.  The kernel computes
# |q|^2 + |v|^2 - 2 q.v in float32 on node-centred vectors whose squared
# norms reach about 4,000 here, where a float32 ulp is 4.9e-4; 2e-2 is 40
# such ulps, room for the 128-term dot product.  (The CPU interpreter stays
# under 4e-3 on this data; a single bfloat16 pass would be off by units.)
TIE_ATOL = 2e-2
TIE_RTOL = 1e-5

SERVED_PATHS = ("batched", "batched+packed")
SHARDED_PATHS = ("sharded", "sharded+packed")
PAPER_LIKE = dict(block_zipf=(1.0, 2.0), perm_zipf=(2.0, 1.5))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Scale of one smoke run; the defaults are the deployment size."""

    n_vectors: int = 1_000_000
    dim: int = 128
    n_roles: int = 32
    n_permissions: int = 120
    wide_vectors: int = 100_000
    wide_roles: int = 256
    wide_permissions: int = 280
    n_requests: int = 320
    max_batch: int = 64
    lam_threshold: int = 2900          # HNSWCostModel default (paper Fig. 2)


# ------------------------------------------------------------------ output
def log(msg: str) -> None:
    print(msg, flush=True)


class Clock:
    """Wall time of each phase (smoke timings, not benchmark numbers)."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def lap(self) -> float:
        t, self.t0 = self.t0, time.perf_counter()
        return self.t0 - t


# ------------------------------------------------------------------ oracle
class Oracle:
    """Float64 brute force over the whole corpus: the answers every search is
    held to.  Authorization comes from the policy itself and predicates from
    the raw attribute values, not from the packed words under test."""

    def __init__(self, vectors: np.ndarray, policy,
                 pred_truth=None):
        self.v64 = np.asarray(vectors, np.float64)
        self.vn = np.einsum("nd,nd->n", self.v64, self.v64)
        self.policy = policy
        self.pred_truth = pred_truth          # where -> (N,) bool
        self._masks: Dict = {}

    def allowed(self, roles, where) -> np.ndarray:
        key = (roles, where)
        if key not in self._masks:
            m = np.zeros(len(self.v64), bool)
            for r in roles:
                m |= self.policy.authorized_mask(int(r))
            if where:
                m &= self.pred_truth(where)
            self._masks[key] = m
        return self._masks[key]

    def distances(self, qs: np.ndarray) -> np.ndarray:
        """(B, N) exact squared L2 distances."""
        q = np.asarray(qs, np.float64)
        d = (q * q).sum(1)[:, None] + self.vn[None, :] - 2.0 * (q @ self.v64.T)
        return np.maximum(d, 0.0)


class Checker:
    """Holds every answer to the oracle and tallies what it saw."""

    def __init__(self, label: str, oracle: Oracle,
                 paths: Sequence[str] = SERVED_PATHS):
        self.label = label
        self.oracle = oracle
        self.paths = tuple(paths)
        self.answers = 0
        self.hits = 0
        self.tie_swaps = 0
        self.max_err = 0.0
        self.path_counts: Dict[str, int] = {}
        self.failures: List[str] = []

    def fail(self, msg: str) -> None:
        if len(self.failures) < 20:
            print(f"[{self.label}] MISMATCH {msg}", file=sys.stderr,
                  flush=True)
        self.failures.append(msg)

    def check(self, queries, results, chunk: int = 32) -> None:
        if len(results) != len(queries):
            self.fail(f"{len(results)} results for {len(queries)} queries")
            return
        for lo in range(0, len(queries), chunk):
            qs = queries[lo:lo + chunk]
            dist = self.oracle.distances(np.stack([q.vector for q in qs]))
            for j, (q, res) in enumerate(zip(qs, results[lo:lo + chunk])):
                self._one(lo + j, q, res, dist[j])

    def _one(self, n: int, q, res, d64: np.ndarray) -> None:
        self.answers += 1
        self.path_counts[res.path] = self.path_counts.get(res.path, 0) + 1
        if res.path not in self.paths:
            self.fail(f"query {n}: path {res.path!r}, want {self.paths}")
        allowed = self.oracle.allowed(q.roles, q.where)
        cand = np.flatnonzero(allowed)
        want = min(q.k, len(cand))
        ids = np.asarray(res.ids, np.int64)
        dists = np.asarray(res.dists, np.float64)
        self.hits += len(ids)
        if len(ids) != want:
            self.fail(f"query {n}: {len(ids)} hits, want {want}")
            return
        if not want:
            return
        cd = d64[cand]
        top = np.argpartition(cd, want - 1)[:want] if want < len(cd) \
            else np.arange(len(cd))
        top = top[np.lexsort((cand[top], cd[top]))]
        t_ids, t_d = cand[top], cd[top]
        if not allowed[ids].all():
            self.fail(f"query {n}: unauthorized ids "
                      f"{ids[~allowed[ids]].tolist()}")
            return
        if len(set(ids.tolist())) != len(ids):
            self.fail(f"query {n}: duplicate ids {ids.tolist()}")
        if (np.diff(dists) < 0).any():
            self.fail(f"query {n}: distances not sorted")
        tol = TIE_ATOL + TIE_RTOL * float(t_d[-1])
        exact = d64[ids]
        err = np.abs(dists - exact)
        self.max_err = max(self.max_err, float(err.max()))
        if (err > tol).any():
            i = int(err.argmax())
            self.fail(f"query {n}: id {ids[i]} distance {dists[i]} vs "
                      f"exact {exact[i]} (tol {tol:.3g})")
        swapped = ids != t_ids
        if swapped.any():
            gap = np.abs(exact[swapped] - t_d[swapped])
            if (gap > tol).any():
                self.fail(f"query {n} (roles {q.roles}, k {q.k}): ids "
                          f"{ids[swapped].tolist()} where the oracle has "
                          f"{t_ids[swapped].tolist()} (gap {gap.max():.4g}"
                          f" > tol {tol:.3g})")
            self.tie_swaps += int(swapped.sum())

    def summary(self) -> str:
        return (f"answers={self.answers} hits={self.hits} "
                f"mismatches={len(self.failures)} tie_swaps={self.tie_swaps}"
                f" max|d-d64|={self.max_err:.3g} "
                f"tol={TIE_ATOL}+{TIE_RTOL}*d_k paths={self.path_counts}")


# -------------------------------------------------------------- the stores
def make_main_store(sizes: Sizes, seed: int):
    """The SIFT1M-shaped store with the benchmark's paper-like skew."""
    from repro.ann.scorescan import scorescan_factory
    from repro.core import HNSWCostModel, build_effveda, build_vector_storage
    from repro.data import make_retrieval_dataset
    ds = make_retrieval_dataset(
        n_vectors=sizes.n_vectors, dim=sizes.dim, n_roles=sizes.n_roles,
        n_permissions=sizes.n_permissions, n_queries=512, seed=seed,
        **PAPER_LIKE)
    cm = HNSWCostModel(lam_threshold=sizes.lam_threshold)
    result = build_effveda(ds.policy, cm, beta=1.1, k=10)
    store = build_vector_storage(
        result, ds.vectors, engine_factory=scorescan_factory(ds.policy),
        pack_leftovers=True)
    return ds, store


def describe(store) -> str:
    sizes = sorted(len(e) for e in store.engines.values()) or [0]
    shard = store.leftover_shard
    return (f"{len(store.data)}x{store.data.shape[1]} nodes={len(sizes)} "
            f"rows/node={sizes[0]}..{sizes[-1]}"
            f" leftover_blocks={len(store.leftover_ids)} "
            f"packed_rows={len(shard) if shard is not None else 0} "
            f"W={store.mask_width} P={store.pred_width} sa={store.sa():.3f}")


def role_queries(ds, rng, n: int, k, union: bool = False,
                 where_pool=None) -> list:
    """Queries from the dataset's own query vectors; ``k`` is an int or a
    sequence cycled over the batch, ``union`` adds a second random role."""
    from repro.core import Query
    ks = [k] if isinstance(k, int) else list(k)
    n_roles = ds.policy.n_roles
    out = []
    for i in range(n):
        j = int(rng.integers(len(ds.queries)))
        roles = (int(ds.query_roles[j]),)
        if union:
            roles += (int((roles[0] + 1 + rng.integers(n_roles - 1))
                          % n_roles),)
        where = None if where_pool is None else \
            where_pool[int(rng.integers(len(where_pool)))]
        out.append(Query(vector=ds.queries[j], roles=roles,
                         k=ks[i % len(ks)], where=where))
    return out


def check_compiled(engine, k: int) -> bool:
    """Lower one engine's launch through the kernel wrapper, as the engine
    calls it, and report whether the compiled program holds the Mosaic
    kernel (on a TPU backend, an interpreted kernel would not)."""
    import jax
    from repro.kernels.l2_topk import l2_topk
    b = engine.config.bq
    q = jax.ShapeDtypeStruct((b, engine.data.shape[1]), np.float32)
    db = jax.ShapeDtypeStruct(engine.data.shape, np.float32)
    auth = jax.ShapeDtypeStruct(engine.auth_bits.shape, np.uint32)
    masks = jax.ShapeDtypeStruct((b,) + engine.auth_bits.shape[1:],
                                 np.uint32)
    fn = jax.jit(lambda q, db, a, m: l2_topk(q, db, a, m, k,
                                             config=engine.config))
    return "tpu_custom_call" in fn.lower(q, db, auth, masks).compile() \
        .as_text()


async def _serve(store, queries, max_batch: int):
    from repro.launch.scheduler import MicroBatchScheduler, serve_requests
    sched = MicroBatchScheduler(store, max_batch=max_batch)
    try:
        outcomes = await serve_requests(sched, queries)
    finally:
        await sched.close()
    return outcomes, sched.stats


# ----------------------------------------------------------- one-chip run
def run_single(sizes: Sizes, seed: int) -> bool:
    """Phases 1-6 of the module docstring; True when every check held."""
    import jax
    from repro.core import SearchResult
    from repro.launch.serve import warm_batch_shapes
    from repro.obs import CompileCounter

    clock = Clock()
    compiles = CompileCounter()
    rng = np.random.default_rng(seed + 7)
    ds, store = make_main_store(sizes, seed)
    log(f"[main] built {describe(store)} ({clock.lap():.2f} s)")
    oracle = Oracle(ds.vectors, ds.policy)
    checker = Checker("main", oracle)
    log(f"[main] float64 oracle ready ({clock.lap():.2f} s)")

    snap = compiles.snapshot()
    biggest = max(store.engines.values(), key=len)
    on_tpu = jax.default_backend() == "tpu"
    if check_compiled(biggest, 10) != on_tpu:
        print(f"[kernel] on {jax.default_backend()} the launch should "
              f"{'' if on_tpu else 'not '}hold tpu_custom_call: the kernel "
              f"is not running in the backend's mode", file=sys.stderr)
        return False
    log(f"[kernel] l2_topk launch over {len(biggest)} rows "
        f"{'compiles to' if on_tpu else 'interprets, without'} "
        f"tpu_custom_call ({compiles.since(snap)}; {clock.lap():.2f} s)")

    snap = compiles.snapshot()
    buckets = tuple(range(8, sizes.max_batch + 1, 8))
    calls = warm_batch_shapes(store, sizes=buckets, k=10)
    for k in (1, 100):
        calls += warm_batch_shapes(store, sizes=(8,), k=k)
    log(f"[warmup] set-up: {calls} warm launches, {compiles.since(snap)} "
        f"({clock.lap():.2f} s)")

    batches = [
        ("single k=10", role_queries(ds, rng, 32, 10)),
        ("union k=10", role_queries(ds, rng, 32, 10, union=True)),
        ("single k=1", role_queries(ds, rng, 8, 1)),
        ("single k=100", role_queries(ds, rng, 8, 100)),
        ("union k=100", role_queries(ds, rng, 8, 100, union=True)),
        ("mixed k", role_queries(ds, rng, 8, (1, 10, 100))),
    ]
    snap = compiles.snapshot()
    for name, qs in batches:
        t0 = time.perf_counter()
        results = store.search(qs)
        dt = time.perf_counter() - t0
        checker.check(qs, results)
        log(f"[search] {name}: {len(qs)} queries, paths="
            f"{sorted({r.path for r in results})} ({dt:.3f} s)")
    log(f"[search] {compiles.since(snap)} ({clock.lap():.2f} s)")

    snap = compiles.snapshot()
    reqs = (role_queries(ds, rng, sizes.n_requests // 2, 10)
            + role_queries(ds, rng, sizes.n_requests // 2, 10, union=True))
    order = rng.permutation(len(reqs))
    reqs = [reqs[i] for i in order]
    t0 = time.perf_counter()
    outcomes, stats = asyncio.run(_serve(store, reqs, sizes.max_batch))
    dt = time.perf_counter() - t0
    bad = [o for o in outcomes if not isinstance(o, SearchResult)]
    if bad:
        checker.fail(f"{len(bad)} requests resolved without a result: "
                     f"{bad[:3]}")
    checker.check(reqs, outcomes)
    log(f"[serve] {len(reqs)} requests: flushes={stats.batches_flushed} "
        f"avg_batch={stats.avg_batch:.1f} paths={stats.paths} "
        f"failed={stats.failed} ({dt:.3f} s; {compiles.since(snap)})")
    log(f"[main] {checker.summary()} ({clock.lap():.2f} s)")

    wide = run_wide(sizes, seed, rng, compiles, clock)
    return not checker.failures and wide


def run_wide(sizes: Sizes, seed: int, rng, compiles, clock) -> bool:
    """Phase 6: 256 roles (W=8) with a 2-word predicate plane (P=2)."""
    from repro.ann.scorescan import scorescan_factory
    from repro.core import HNSWCostModel, build_effveda, build_vector_storage
    from repro.core.predicate import PredicateSchema
    from repro.data import make_retrieval_dataset

    ds = make_retrieval_dataset(
        n_vectors=sizes.wide_vectors, dim=sizes.dim, n_roles=sizes.wide_roles,
        n_permissions=sizes.wide_permissions, n_queries=256, seed=seed + 1,
        **PAPER_LIKE)
    # 40 tenant tags + 1 never-assigned tag + 8 time edges = 49 bits: P = 2
    tenants = tuple(f"t{i}" for i in range(40)) + ("never",)
    edges = tuple(float(e) for e in range(0, 80, 10))
    schema = PredicateSchema.make(tags={"tenant": tenants},
                                  ranges={"ts": edges})
    attr_rng = np.random.default_rng(seed + 2)
    tenant = attr_rng.integers(0, 40, sizes.wide_vectors)
    ts = attr_rng.uniform(0.0, 80.0, sizes.wide_vectors)
    attrs = np.zeros((sizes.wide_vectors, schema.n_words), np.uint32)
    for rows, bit in ([(tenant == t, schema.bit_of("tenant", f"t{t}"))
                       for t in range(40)]
                      + [(ts >= e, schema.bit_of("ts", e)) for e in edges]):
        attrs[rows, bit // 32] |= np.uint32(1 << (bit % 32))
    cm = HNSWCostModel(lam_threshold=sizes.lam_threshold)
    result = build_effveda(ds.policy, cm, beta=1.1, k=10)
    store = build_vector_storage(
        result, ds.vectors,
        engine_factory=scorescan_factory(ds.policy, attr_words=attrs),
        pack_leftovers=True, pred_schema=schema, attr_words=attrs)
    log(f"[wide] built {describe(store)} ({clock.lap():.2f} s)")

    def truth(where) -> np.ndarray:
        ok = np.ones(sizes.wide_vectors, bool)
        for op, field, value in where:
            if field == "tenant":
                has = tenant == (int(value[1:]) if value != "never" else -1)
                ok &= has if op == "has" else ~has
            else:
                ok &= (ts >= value) if op == "ge" else (ts < value)
        return ok

    pool = [None,
            (("has", "tenant", "t3"),),
            (("lacks", "tenant", "t5"), ("ge", "ts", 20.0)),
            (("ge", "ts", 10.0), ("lt", "ts", 50.0)),
            (("has", "tenant", "t7"), ("lt", "ts", 40.0)),
            (("has", "tenant", "never"),)]
    checker = Checker("wide", Oracle(ds.vectors, ds.policy, truth))
    batches = [
        ("filtered single k=10", role_queries(ds, rng, 32, 10,
                                              where_pool=pool)),
        ("filtered union k=10", role_queries(ds, rng, 16, 10, union=True,
                                             where_pool=pool)),
        ("filtered k=100", role_queries(ds, rng, 8, 100, where_pool=pool)),
    ]
    snap = compiles.snapshot()
    for name, qs in batches:
        t0 = time.perf_counter()
        results = store.search(qs)
        dt = time.perf_counter() - t0
        checker.check(qs, results)
        log(f"[wide] {name}: {len(qs)} queries, paths="
            f"{sorted({r.path for r in results})} ({dt:.3f} s)")
    log(f"[wide] {compiles.since(snap)} (unwarmed: the predicate kernels "
        f"compile here)")
    log(f"[wide] {checker.summary()} ({clock.lap():.2f} s)")
    return not checker.failures


# ------------------------------------------------------------ sharded run
class LaunchPlacement:
    """Wraps the kernel wrapper the device shards call and records, for each
    launch, the devices of its node operand and of its outputs."""

    def __init__(self):
        import repro.kernels.l2_topk as pkg
        self.pkg = pkg
        self.inner = pkg.l2_topk
        self.launches: List = []

    def __enter__(self):
        def recording(queries, db, *args, **kw):
            d, i = self.inner(queries, db, *args, **kw)
            self.launches.append((frozenset(db.devices()),
                                  frozenset(d.devices()) | i.devices()))
            return d, i
        self.pkg.l2_topk = recording
        return self

    def __exit__(self, *exc):
        self.pkg.l2_topk = self.inner


def run_sharded(sizes: Sizes, seed: int, chips: int) -> bool:
    """The ``--chips`` phase: the main store across ``chips`` devices,
    compared with its one-device answers and the oracle."""
    import jax
    from repro.launch.mesh import DeviceMesh
    from repro.launch.serve import warm_batch_shapes
    from repro.obs import CompileCounter

    clock = Clock()
    compiles = CompileCounter()
    rng = np.random.default_rng(seed + 7)
    ds, store = make_main_store(sizes, seed)
    log(f"[main] built {describe(store)} ({clock.lap():.2f} s)")
    mesh = DeviceMesh.host(chips)
    sharded = store.sharded(mesh)
    ok = True
    try:
        shards = list(sharded.device_shards())
        devices = {s.device for s in shards}
        misplaced = [s.key for s in shards for a in
                     (s._data_dev, s._auth_dev, s._attr_dev)
                     if a is not None and a.devices() != {s.device}]
        log(f"[sharded] {mesh.describe()}: {len(shards)} shards on "
            f"{len(devices)} devices, imbalance "
            f"{sharded.placement.imbalance():.3f} ({clock.lap():.2f} s)")
        if mesh.n_physical != chips or len(devices) != chips:
            print(f"[sharded] shards use {len(devices)} devices, want "
                  f"{chips}", file=sys.stderr)
            ok = False
        if misplaced:
            print(f"[sharded] pinned arrays off their shard's device: "
                  f"{misplaced[:5]}", file=sys.stderr)
            ok = False

        snap = compiles.snapshot()
        calls = warm_batch_shapes(sharded, sizes=(64,), k=10)
        calls += warm_batch_shapes(store, sizes=(64,), k=10)
        log(f"[warmup] set-up: {calls} warm launches, "
            f"{compiles.since(snap)} ({clock.lap():.2f} s)")

        oracle = Oracle(ds.vectors, ds.policy)
        one = Checker("one-device", oracle)
        many = Checker("sharded", oracle, SHARDED_PATHS)
        batches = [
            ("mixed k=10", role_queries(ds, rng, 32, 10)
             + role_queries(ds, rng, 32, 10, union=True)),
            ("union k=100", role_queries(ds, rng, 8, 100, union=True)),
        ]
        used_all = set()
        for name, qs in batches:
            with LaunchPlacement() as placed:
                t0 = time.perf_counter()
                got = sharded.search(qs)
                dt = time.perf_counter() - t0
            ref = store.search(qs)
            many.check(qs, got)
            one.check(qs, ref)
            same = sum(g.hits == r.hits for g, r in zip(got, ref))
            wrong = [p for p in placed.launches if p[1] != p[0]
                     or len(p[0]) != 1]
            used = {next(iter(p[0])) for p in placed.launches}
            used_all |= used
            log(f"[sharded] {name}: {len(qs)} queries, {same} identical to "
                f"one device, {len(placed.launches)} launches on "
                f"{len(used)} devices, {len(wrong)} off-device outputs "
                f"({dt:.3f} s)")
            if same != len(qs) or wrong:
                ok = False
        per_slot = [int(v["launches"])
                    for v in sharded.device_stats().values()]
        log(f"[sharded] launches per slot {per_slot} on {len(used_all)} "
            f"devices; {compiles.since(snap)}")
        if len(used_all) != chips or min(per_slot) == 0:
            print(f"[sharded] launches reached {len(used_all)} of {chips} "
                  f"devices", file=sys.stderr)
            ok = False
        log(f"[one-device] {one.summary()}")
        log(f"[sharded] {many.summary()} ({clock.lap():.2f} s)")
        ok = ok and not one.failures and not many.failures
    finally:
        sharded.close()
    return ok


# ------------------------------------------------------------------ entry
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase across four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's default device is {dev.platform!r}, not a "
              f"TPU; nothing was run", file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x{len(devices)}")
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" found {len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")

    t0 = time.perf_counter()
    ok = (run_sharded(Sizes(), args.seed, args.chips) if args.chips > 1
          else run_single(Sizes(), args.seed))
    log(f"total {time.perf_counter() - t0:.2f} s")
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
