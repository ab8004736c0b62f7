"""The system under test, reached only through its public entry points.

This is the one module of the benchmark that imports the program: it turns
the benchmark's raw data into the program's ``AccessPolicy``, builds the
store with the configuration's lattice settings (``build_effveda`` + ``build_vector_storage`` with
ScoreScan engines and the packed leftover shard), wraps the instances the
window drives with the benchmark's spans, warms the shapes the cell's
traffic reaches, and hands out the program's scheduler.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from .corpus import PolicyDraw, QuerySpec
from .reference import Answer
from .spans import SpanRecorder, wrap_engine, wrap_search


@dataclasses.dataclass
class Built:
    store: object
    engines: List[object]          # node engines, then the packed shard


def to_policy(draw: PolicyDraw):
    from repro.core import AccessPolicy
    members = tuple(draw.members(b) for b in range(len(draw.block_roles)))
    return AccessPolicy(n_roles=draw.n_roles, block_roles=draw.block_roles,
                        block_members=members)


def build(config: Dict, vectors: np.ndarray, draw: PolicyDraw) -> Built:
    from repro.ann.scorescan import scorescan_factory
    from repro.core import (HNSWCostModel, build_effveda,
                            build_vector_storage)
    lat = config["lattice"]
    policy = to_policy(draw)
    cm = HNSWCostModel(lam_threshold=lat["lam_threshold"])
    result = build_effveda(policy, cm, beta=lat["beta"], k=lat["k"])
    store = build_vector_storage(
        result, vectors,
        engine_factory=scorescan_factory(policy),
        pack_leftovers=lat["pack_leftovers"])
    engines = [e for e in store.engines.values() if len(e)]
    if store.leftover_shard is not None and len(store.leftover_shard):
        engines.append(store.leftover_shard)
    return Built(store=store, engines=engines)


def describe(built: Built) -> str:
    store = built.store
    sizes = sorted(len(e) for e in store.engines.values()) or [0]
    shard = store.leftover_shard
    padded = {-(-n // 512) * 512 for n in sizes}
    return (f"{len(store.data)}x{store.data.shape[1]} nodes={len(sizes)} "
            f"rows/node={sizes[0]}..{sizes[-1]} padded_sizes={len(padded)} "
            f"leftover_blocks={len(store.leftover_ids)} "
            f"packed_rows={len(shard) if shard is not None else 0} "
            f"W={store.mask_width} P={store.pred_width} "
            f"sa={store.sa():.4f}")


def instrument(built: Built, rec: SpanRecorder) -> None:
    wrap_search(built.store, rec)
    for eng in built.engines:
        wrap_engine(eng, rec)


def to_query(spec: QuerySpec):
    from repro.core import Query
    return Query(vector=spec.vector, roles=spec.roles, k=spec.k)


def to_answer(outcome) -> Optional[Answer]:
    from repro.core import SearchResult
    if not isinstance(outcome, SearchResult):
        return None
    return Answer(ids=np.asarray(outcome.ids, np.int64),
                  dists=np.asarray(outcome.dists, np.float64))


def warm(built: Built, k: int, max_batch: int, dim: int,
         seed: int = 0) -> int:
    """Call every engine through the ``BatchEngine`` protocol at each
    padded query bucket up to ``max_batch``; then call the smallest node at
    every batch size from 1 to ``max_batch``, with bounds as node waves
    pass them and without as the packed shard's launch does: the kernel
    wrapper's host-side operations take the unpadded size.  Returns the
    number of calls."""
    store = built.store
    rng = np.random.default_rng(seed)
    qs = rng.standard_normal((max_batch, dim)).astype(np.float32)
    masks = store.role_mask_rows([(0,)] * max_batch)
    calls = 0

    def one(eng, b, bounded=True):
        bounds = np.full(b, np.inf, np.float32) if bounded else None
        eng.search_masked_batch(qs[:b], k, masks[:b], bounds=bounds)

    shard = store.leftover_shard
    for eng in built.engines:
        bq = eng.config.bq
        for b in range(bq, max_batch + bq, bq):
            one(eng, min(b, max_batch), bounded=eng is not shard)
            calls += 1
    nodes = [e for e in built.engines if e is not shard]
    if nodes:
        small = min(nodes, key=len)
        for b in range(1, max_batch + 1):
            for bounded in (True, False):
                one(small, b, bounded)
                calls += 1
    return calls


def scheduler(store, traffic: Dict):
    from repro.launch.scheduler import MicroBatchScheduler
    return MicroBatchScheduler(store, max_batch=traffic["max_batch"],
                               max_wait_ms=traffic["max_wait_ms"])
