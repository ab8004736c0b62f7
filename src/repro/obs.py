"""Spans and counters of the program's own work, on the host clock.

One recorder for the whole process, always on.  ``span(name, **attrs)``
times a block of work by ``time.perf_counter`` (the clock the scheduler,
``ServeStats`` and the benchmark use) and, for its duration, enters a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
lays the device's operations beside it.  Each span knows the span that
was open on its thread when it began (``parent``) and the outermost one
(``root``: the scheduler's ``serve.flush`` for everything a flush does).
Work handed to another thread stays in its tree through ``within``.
``count(name, n)`` adds to a cumulative counter and to the attributes of
the innermost span open on the calling thread, so a counter can be summed
per launch or per flush.

When a root closes, the recorder keeps two things of its tree:

- a ``Root``: the root span with the seconds of its tree's spans summed
  by name and its counters summed, in a ring of ``ROOTS`` roots.  Per-flush
  readers use these, so what they read does not depend on how many spans
  a flush opens or how long a window runs;
- the tree's spans themselves, while the newest trees together hold at
  most ``RING`` spans; older trees are let go whole.

``roots(since=t)`` and ``spans(since=t)`` return ``None`` when something
that began at or after ``t`` was let go, so that a reader never sums a
partial interval; ``held_from()`` says after when every tree is still
held.  ``slowest(n)`` gives the trees of the n slowest flushes held.

XLA compiles are counted here too (``compiles``, ``compile_s``,
``compile_cache_hits``) by a ``jax.monitoring`` listener, on the innermost
span open on the compiling thread: the step that compiled says so.

DESIGN.md §Observability lists the spans and counters the program opens.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any, Dict, List, Optional

import jax

RING = 65536        # spans held in whole trees
ROOTS = 16384       # root summaries held
OPEN = 1024         # roots with closed spans whose root is still open


class Span:
    """One timed block of work; also the context manager that times it."""

    __slots__ = ("name", "t0", "t1", "id", "parent", "root", "thread",
                 "attrs", "_note")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = 0.0
        self.id = self.parent = self.root = self.thread = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        stack = _stack()
        self.id = next(_ids)
        top = stack[-1] if stack else None
        self.parent = top.id if top else None
        self.root = top.root if top else self.id
        self.thread = threading.get_ident()
        stack.append(self)
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._note.__exit__(*exc)
        self._note = None
        _stack().pop()
        _REC.add(self)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.seconds * 1e3:.3f} ms, "
                f"id={self.id}, parent={self.parent}, root={self.root}, "
                f"{self.attrs})")


@dataclasses.dataclass
class Root:
    """A closed root span and the sums of its tree."""
    span: Span
    seconds: Dict[str, float]      # span name -> summed seconds, root too
    counts: Dict[str, float]       # counter -> summed over the tree's spans
    spans: Optional[List[Span]]    # the whole tree while it is held


class Recorder:
    """Closed spans, grouped into trees by root (module docstring)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.counters: Dict[str, float] = collections.defaultdict(int)
        self.open: Dict[int, List[Span]] = {}
        self._roots: collections.deque = collections.deque()
        self._trees: collections.deque = collections.deque()
        self.held = 0                       # spans in self._trees
        self.dropped = 0                    # roots let go
        self.dropped_t0 = float("-inf")     # latest start of one let go
        self.freed_t0 = float("-inf")       # same, of the trees let go
        self.freed_t1 = float("-inf")       # latest end of a tree let go

    def add(self, span: Span) -> None:
        with self.lock:
            if span.parent is not None:
                self.open.setdefault(span.root, []).append(span)
                if len(self.open) > OPEN:   # a root that never closes
                    self.open.pop(next(iter(self.open)))
                return
            tree = self.open.pop(span.id, [])
            tree.append(span)
            self._keep(Root(span, *self._sums(tree), spans=tree))

    def _sums(self, tree: List[Span]):
        seconds: Dict[str, float] = collections.defaultdict(float)
        counts: Dict[str, float] = collections.defaultdict(int)
        for s in tree:
            seconds[s.name] += s.seconds
            for k, v in s.attrs.items():
                if k in self.counters:
                    counts[k] += v
        return dict(seconds), dict(counts)

    def _keep(self, root: Root) -> None:
        self._roots.append(root)
        self._trees.append(root)
        self.held += len(root.spans)
        if len(self._roots) > ROOTS:
            old = self._roots.popleft()
            self.dropped += 1
            self.dropped_t0 = max(self.dropped_t0, old.span.t0)
            if old.spans is not None:     # the held trees are the newest
                self._free(self._trees.popleft())
        while self.held > RING:
            self._free(self._trees.popleft())

    def _free(self, root: Root) -> None:
        self.held -= len(root.spans)
        root.spans = None
        self.freed_t0 = max(self.freed_t0, root.span.t0)
        self.freed_t1 = max(self.freed_t1, root.span.t1)

    def count(self, name: str, n: float) -> None:
        with self.lock:
            self.counters[name] += n

    def roots(self, since: float) -> Optional[List[Root]]:
        with self.lock:
            if self.dropped_t0 >= since:
                return None
            return [r for r in self._roots if r.span.t0 >= since]

    def spans(self, since: float) -> Optional[List[Span]]:
        with self.lock:
            if self.freed_t0 >= since:
                return None
            return [s for r in self._trees if r.span.t0 >= since
                    for s in r.spans]

    def held_from(self) -> float:
        return self.freed_t1


_REC = Recorder()
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> List[Span]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def recorder() -> Recorder:
    """The process's recorder."""
    return _REC


def span(name: str, **attrs) -> Span:
    """Context manager timing a block as span ``name`` (see the module
    docstring); ``as`` gives the span, whose ``set`` adds attributes."""
    return Span(name, attrs)


def current() -> Optional[Span]:
    """The innermost span open on this thread."""
    stack = _stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def within(parent: Optional[Span]):
    """Spans opened inside, on this thread, belong under ``parent``, a span
    open on another thread (``current()`` there): work handed to a worker
    thread stays in its flush's tree.  Open a span of this thread's own
    before counting."""
    if parent is None:
        yield
        return
    stack = _stack()
    stack.append(parent)
    try:
        yield
    finally:
        stack.pop()


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` and to that attribute of the innermost
    span open on this thread."""
    _REC.count(name, n)
    stack = _stack()
    if stack:
        attrs = stack[-1].attrs
        attrs[name] = attrs.get(name, 0) + n


def counters() -> Dict[str, float]:
    """Cumulative counters since the process started."""
    with _REC.lock:
        return dict(_REC.counters)


def dropped() -> int:
    """Roots, with their trees, let go since the process started."""
    return _REC.dropped


def roots(since: float = float("-inf")) -> Optional[List[Root]]:
    """Roots that began at or after ``since`` (perf_counter seconds), in
    the order they closed; ``None`` if one of them was let go."""
    return _REC.roots(since)


def spans(since: float = float("-inf")) -> Optional[List[Span]]:
    """The spans of the trees whose root began at or after ``since``, tree
    by tree in the order the roots closed; ``None`` if one of those trees
    was let go."""
    return _REC.spans(since)


def held_from() -> float:
    """Every tree whose root began after this time is held."""
    return _REC.held_from()


def tree(root: Span, members: List[Span]) -> Dict[str, Any]:
    """``root`` and the spans under it as nested dicts: name, ms, attrs,
    children in start order."""
    kids: Dict[int, List[Span]] = collections.defaultdict(list)
    for s in members:
        kids[s.parent].append(s)

    def node(s: Span) -> Dict[str, Any]:
        return {"name": s.name, "ms": s.seconds * 1e3, "attrs": dict(s.attrs),
                "children": [node(c) for c in
                             sorted(kids[s.id], key=lambda c: c.t0)]}
    return node(root)


def slowest(n: int = 3, root: str = "serve.flush",
            since: float = float("-inf")) -> List[Dict[str, Any]]:
    """Trees of the ``n`` slowest ``root`` spans that began at or after
    ``since`` and are still held, slowest first: the operator's view of a
    stall."""
    with _REC.lock:
        held = [(r.span, r.spans) for r in _REC._trees
                if r.span.name == root and r.span.t0 >= since]
    held.sort(key=lambda t: -t[0].seconds)
    return [tree(top, members) for top, members in held[:n]]


class CompileCounter:
    """Snapshots of the compile counters, for logs: ``since(snapshot())``
    reads the compiles after the snapshot."""

    def snapshot(self):
        c = counters()
        return (c.get("compiles", 0), c.get("compile_cache_hits", 0),
                c.get("compile_s", 0.0))

    def since(self, snap) -> str:
        r, h, s = (a - b for a, b in zip(self.snapshot(), snap))
        return (f"compiles={int(r)} cache_hits={int(h)} fresh={int(r - h)} "
                f"compile_s={s:.3f}")


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        count("compile_cache_hits")


def _on_duration(event: str, duration: float, **_) -> None:
    # every compile request, a persistent-cache read included
    if event == "/jax/core/compile/backend_compile_duration":
        count("compiles")
        count("compile_s", duration)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
