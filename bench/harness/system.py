"""The system under test, reached only through its public entry points.

This is the one module of the benchmark that imports the program: it turns
the benchmark's raw data into the program's ``AccessPolicy`` and, where the
configuration declares ``predicates``, into its ``PredicateSchema`` and
packed attribute words; builds the store with the configuration's lattice
settings (``build_effveda`` + ``build_vector_storage`` with ScoreScan
engines and the packed leftover shard); turns a drawn query, its ``where``
clause with it, into the program's ``Query``; wraps the instances the
window drives with the benchmark's spans, warms the shapes the cell's
traffic reaches, and hands out the program's scheduler.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from .corpus import TAG_OPS, Attributes, PolicyDraw, QuerySpec
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


def to_schema(predicates: Dict):
    """The program's schema for the configuration's ``predicates``: a tag
    field's values are named by their index, a range field's bits are its
    edges."""
    from repro.core.predicate import PredicateSchema
    return PredicateSchema.make(
        tags={f: tuple(str(v) for v in range(spec["values"]))
              for f, spec in predicates.get("tags", {}).items()},
        ranges={f: spec["edges"]
                for f, spec in predicates.get("ranges", {}).items()})


def attr_words(schema, attrs: Attributes) -> np.ndarray:
    """(N, P) uint32 attribute words: each row's tag value bit, and the
    thermometer bit of every range edge at or below its value."""
    words = np.zeros((attrs.n_rows, schema.n_words), np.uint32)

    def set_bit(rows: np.ndarray, bit: int) -> None:
        words[rows, bit // 32] |= np.uint32(1 << (bit % 32))
    for f, index in attrs.tags.items():
        for v in np.unique(index):
            set_bit(index == v, schema.bit_of(f, str(v)))
    for f, values in attrs.ranges.items():
        for edge in dict(schema.range_fields)[f]:
            set_bit(values >= edge, schema.bit_of(f, edge))
    return words


def build(config: Dict, vectors: np.ndarray, draw: PolicyDraw,
          attrs: Optional[Attributes] = None) -> Built:
    from repro.ann.scorescan import scorescan_factory
    from repro.core import (HNSWCostModel, build_effveda,
                            build_vector_storage)
    lat = config["lattice"]
    policy = to_policy(draw)
    cm = HNSWCostModel(lam_threshold=lat["lam_threshold"])
    result = build_effveda(policy, cm, beta=lat["beta"], k=lat["k"])
    plane: Dict = {}
    if attrs is not None:
        schema = to_schema(config["predicates"])
        plane = dict(pred_schema=schema, attr_words=attr_words(schema, attrs))
    store = build_vector_storage(
        result, vectors,
        engine_factory=scorescan_factory(
            policy, attr_words=plane.get("attr_words")),
        pack_leftovers=lat["pack_leftovers"], **plane)
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
    """The program's ``Query``; a tag atom names its value by index."""
    from repro.core import Query
    where = None if spec.where is None else tuple(
        (op, f, str(v) if op in TAG_OPS else v) for op, f, v in spec.where)
    return Query(vector=spec.vector, roles=spec.roles, k=spec.k, where=where)


def to_answer(outcome) -> Optional[Answer]:
    from repro.core import SearchResult
    if not isinstance(outcome, SearchResult):
        return None
    return Answer(ids=np.asarray(outcome.ids, np.int64),
                  dists=np.asarray(outcome.dists, np.float64))


def warm(built: Built, k: int, max_batch: int, dim: int,
         seed: int = 0, kinds: Sequence[bool] = (False,)) -> int:
    """Call every engine through the ``BatchEngine`` protocol at each
    padded query bucket up to ``max_batch``; then call the smallest node at
    every batch size from 1 to ``max_batch``, with bounds as node waves
    pass them and without as the packed shard's launch does: the kernel
    wrapper's host-side operations take the unpadded size.  A flush that
    holds a filtered query passes require/forbid rows to every launch, so
    each call is made once for each of the flush ``kinds`` the mix makes
    (``corpus.flush_kinds``): unfiltered, and with require/forbid rows.
    Returns the number of calls."""
    store = built.store
    rng = np.random.default_rng(seed)
    qs = rng.standard_normal((max_batch, dim)).astype(np.float32)
    masks = store.role_mask_rows([(0,)] * max_batch)
    words = np.zeros((max_batch, store.pred_width), np.uint32)
    calls = 0

    def one(eng, b, bounded, filtered):
        bounds = np.full(b, np.inf, np.float32) if bounded else None
        pred = dict(require=words[:b], forbid=words[:b]) if filtered else {}
        eng.search_masked_batch(qs[:b], k, masks[:b], bounds=bounds, **pred)

    shard = store.leftover_shard
    for eng in built.engines:
        bq = eng.config.bq
        for b in range(bq, max_batch + bq, bq):
            for filtered in kinds:
                one(eng, min(b, max_batch), eng is not shard, filtered)
                calls += 1
    nodes = [e for e in built.engines if e is not shard]
    if nodes:
        small = min(nodes, key=len)
        for b in range(1, max_batch + 1):
            for bounded in (True, False):
                for filtered in kinds:
                    one(small, b, bounded, filtered)
                    calls += 1
    return calls


def scheduler(store, traffic: Dict):
    from repro.launch.scheduler import MicroBatchScheduler
    return MicroBatchScheduler(store, max_batch=traffic["max_batch"],
                               max_wait_ms=traffic["max_wait_ms"])
