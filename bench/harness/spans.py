"""Spans the benchmark records around the program's own calls.

Two boundaries are wrapped on the instances the window drives, so that the
program's call path runs unchanged:

- ``flush``: the store instance's ``search``, one scheduler micro-batch;
- ``launch``: each node engine's (and the packed shard's)
  ``search_masked_batch``, one call of the ``BatchEngine`` protocol, which
  on this program is one kernel launch with its host preparation, copies
  and read-back.

Each span is kept in memory with its host-clock bounds and the shapes of
its work, and is also a ``jax.profiler.TraceAnnotation`` so that a trace
lays the device's activity beside it.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List

FLUSH = "bench.flush"
LAUNCH = "bench.launch"


@dataclasses.dataclass
class Span:
    name: str
    t0: float                 # host clock, seconds (time.perf_counter)
    t1: float
    rows: int                 # queries in the flush / active in the launch
    filtered: int = 0         # flush: queries with a where clause
    n: int = 0                # launch: node rows scanned
    dim: int = 0
    w: int = 0                # launch: auth-mask words
    p: int = 0                # launch: predicate words (0 = unfiltered)
    k: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class SpanRecorder:
    """Collects spans while ``on``; thread-safe appends."""

    def __init__(self):
        self.spans: List[Span] = []
        self.on = False
        self._lock = threading.Lock()

    def add(self, span: Span) -> None:
        if self.on:
            with self._lock:
                self.spans.append(span)

    def clear(self) -> None:
        with self._lock:
            self.spans = []


def wrap_search(store, rec: SpanRecorder) -> None:
    """Record every ``store.search`` call as a flush span."""
    import jax
    inner = store.search

    def search(queries, *args, **kw):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(FLUSH):
            out = inner(queries, *args, **kw)
        rec.add(Span(FLUSH, t0, time.perf_counter(), rows=len(queries),
                     filtered=sum(bool(q.where) for q in queries)))
        return out
    store.search = search


def wrap_engine(engine, rec: SpanRecorder) -> None:
    """Record every ``search_masked_batch`` call of ``engine`` as a launch
    span carrying the shape of its work."""
    import jax
    inner = engine.search_masked_batch
    n = len(engine)
    dim = int(engine.data.shape[1])
    w = int(getattr(engine, "mask_width", 1))

    def search_masked_batch(qs, k, role_masks, bounds=None, **kw):
        words = kw.get("require")
        p = 0 if words is None else int(words.shape[1])
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(LAUNCH):
            out = inner(qs, k, role_masks, bounds=bounds, **kw)
        rec.add(Span(LAUNCH, t0, time.perf_counter(), rows=len(qs), n=n,
                     dim=dim, w=w, p=p, k=k))
        return out
    engine.search_masked_batch = search_masked_batch

