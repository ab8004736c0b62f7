"""The program's own spans and counters (``repro.obs``), read beside the
benchmark's.

The program records on ``time.perf_counter``, the clock of the benchmark's
``bench.flush`` spans.  Each ``bench.flush`` runs inside one program root,
the scheduler's ``serve.flush`` span, and the recorder keeps, for every
root, the seconds of its tree's spans summed by name and its counters
summed.  ``per_flush`` reads those sums, so it reads every flush of the
window however many spans a flush opens.

``mapped`` puts the spans of the trees the recorder still holds on the
profiler trace's clock: the offset is the median, over the window's
flushes paired in order, of the ``bench.flush`` event's start in the
trace less the same flush's start in ``run.spans``.  The mapping is
refused (``None``) unless every program ``serve.flush`` root, so mapped,
brackets its ``bench.flush`` event within ``BRACKET_NS``.  Where the
recorder let the trees of the window's first flushes go, the mapped part
starts after the last tree let go (``ClockMap.from_ns``), and readers of
the trace take their shares of that part only.

This module imports nothing of the program but ``repro.obs``; a program
without it, a run without program spans, and a window part of whose roots
the recorder let go all read as ``None``.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .spans import FLUSH

ROOT = "serve.flush"
BRACKET_NS = 1e6


def recorder():
    """The program's recorder; ``None`` for a program without one."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.recorder()


def flush_roots(run) -> Optional[list]:
    """For each ``bench.flush`` span of the window, in order, the program's
    ``serve.flush`` root that holds it; ``None`` unless each has one."""
    rec = recorder()
    flushes = run.flushes()
    if rec is None or not flushes:
        return None
    roots = rec.roots(since=run.window.t0)
    if roots is None:
        return None
    roots = sorted((r for r in roots if r.span.name == ROOT),
                   key=lambda r: r.span.t0)
    starts = [r.span.t0 for r in roots]
    out = []
    for f in flushes:
        # the latest root that began before the flush and ends after it
        i = bisect.bisect_right(starts, f.t0) - 1
        while i >= 0 and roots[i].span.t1 < f.t1:
            i -= 1
        if i < 0:
            return None
        out.append(roots[i])
    return out


def per_flush(run, name: str, counter: bool = False
              ) -> Optional[List[float]]:
    """For each ``bench.flush`` span of the window, the seconds of the
    program's ``name`` spans in its root summed (or, with ``counter``, the
    counter ``name`` summed); ``None`` if no flush has any."""
    roots = flush_roots(run)
    if roots is None:
        return None
    sums = [r.counts if counter else r.seconds for r in roots]
    if not any(name in s for s in sums):
        return None
    return [s.get(name, 0) for s in sums]


def median_per_flush(run, name: str, scale: float, counter: bool = False
                     ) -> Optional[float]:
    got = per_flush(run, name, counter)
    return float(np.median(got)) * scale if got else None


@dataclasses.dataclass
class ClockMap:
    offset_ns: float       # trace ns = perf_counter s * 1e9 + offset_ns
    start_err_ns: float    # median |a flush's own offset - offset_ns|
    spans: List[Tuple[object, float, float]]   # (span, start ns, end ns)
    from_ns: float         # the spans cover every flush that began after


def mapped(run) -> Optional[ClockMap]:
    """The window's program spans on the trace's clock (module docstring)."""
    t = run.trace
    rec = recorder()
    roots = flush_roots(run)
    if t is None or rec is None or roots is None:
        return None
    events = sorted((a, b) for n, a, b in t.spans if n == FLUSH)
    flushes = run.flushes()
    if len(events) != len(flushes):
        return None
    order = sorted(range(len(flushes)), key=lambda i: flushes[i].t0)
    offs = np.array([a - flushes[i].t0 * 1e9
                     for (a, _), i in zip(events, order)])
    off = float(np.median(offs))
    for (a, b), i in zip(events, order):
        r = roots[i].span
        if (r.t0 * 1e9 + off > a + BRACKET_NS
                or r.t1 * 1e9 + off < b - BRACKET_NS):
            return None
    held = max(run.window.t0, rec.held_from())
    prog = rec.spans(since=held)
    if prog is None:
        return None
    return ClockMap(offset_ns=off,
                    start_err_ns=float(np.median(np.abs(offs - off))),
                    spans=[(s, s.t0 * 1e9 + off, s.t1 * 1e9 + off)
                           for s in prog],
                    from_ns=held * 1e9 + off)


def _union(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def idle(trace) -> Optional[List[Tuple[float, float]]]:
    """Stretches of the traced window, in ns, in which the first device
    that ran anything runs no operation."""
    used = trace.used()
    if not used:
        return None
    lo, hi = trace.window
    busy = _union([(max(a, lo), min(b, hi))
                   for _, a, b in trace.devices[used[0]]
                   if b > lo and a < hi])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def overlap_ns(xs: Sequence[Tuple[float, float]],
               ys: Sequence[Tuple[float, float]]) -> float:
    """Total length of the intersection of two sorted, disjoint lists of
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under(run, name: str) -> Optional[Tuple[float, float]]:
    """Seconds in which the device is idle while a program ``name`` span
    is open, the spans mapped onto the trace clock, and the seconds of the
    traced window that the mapped spans cover."""
    if run.trace is None:
        return None
    gaps = idle(run.trace)
    cmap = mapped(run)
    if gaps is None or cmap is None:
        return None
    hi = run.trace.window[1]
    lo = max(run.trace.window[0], cmap.from_ns)
    if hi <= lo:
        return None
    open_ = _union([(max(a, lo), min(b, hi)) for s, a, b in cmap.spans
                    if s.name == name])
    return overlap_ns(gaps, open_) * 1e-9, (hi - lo) * 1e-9
