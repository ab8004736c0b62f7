"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote with
``jax.profiler.ProfileData`` and keeps three things: the operations each
device ran (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), the
benchmark's own host spans (``bench.*`` annotations), and the traced
window (the ``bench.window`` annotation).  ``Trace`` then reduces them;
it is built from plain tuples, so a test can hand-build one.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Pattern, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Event]]       # device ordinal -> its operations
    spans: List[Event]                    # benchmark host spans
    window: Tuple[float, float]           # traced window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, events: Sequence[Event]) -> List[Tuple[float, float]]:
        lo, hi = self.window
        return [(max(a, lo), min(b, hi)) for _, a, b in events
                if b > lo and a < hi]

    def used(self) -> List[int]:
        return sorted(d for d, ev in self.devices.items()
                      if self._clipped(ev))

    def busy_s(self) -> float:
        """Seconds in which any operation ran, averaged over the devices
        that ran one in the window."""
        used = self.used()
        if not used:
            return 0.0
        total = sum(sum(b - a for a, b in _union(self._clipped(
            self.devices[d]))) for d in used)
        return total / len(used) * 1e-9

    def op_seconds(self, pattern: Pattern) -> float:
        """Summed device time of the operations whose name matches,
        inside the window, over every device."""
        lo, hi = self.window
        return sum(min(b, hi) - max(a, lo)
                   for ev in self.devices.values() for n, a, b in ev
                   if pattern.search(n) and b > lo and a < hi) * 1e-9

    def op_names(self) -> Dict[str, float]:
        """Seconds by operation name, inside the window."""
        lo, hi = self.window
        out: Dict[str, float] = defaultdict(float)
        for ev in self.devices.values():
            for n, a, b in ev:
                if b > lo and a < hi:
                    out[n] += (min(b, hi) - max(a, lo)) * 1e-9
        return dict(out)

    def gaps(self) -> List[Tuple[str, float]]:
        """Idle stretches of the first used device inside the window, each
        labelled by the innermost benchmark span open at its midpoint:
        ``launch``, ``flush.engine`` (a flush outside any launch) or
        ``no-flush``."""
        used = self.used()
        if not used:
            return []
        busy = _union(self._clipped(self.devices[used[0]]))
        lo, hi = self.window
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        launches = [(a, b) for n, a, b in self.spans if n.endswith("launch")]
        flushes = [(a, b) for n, a, b in self.spans if n.endswith("flush")]
        out = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            if any(s <= mid <= e for s, e in launches):
                label = "launch"
            elif any(s <= mid <= e for s, e in flushes):
                label = "flush.engine"
            else:
                label = "no-flush"
            out.append((label, (b - a) * 1e-9))
        return out

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_names().items(), key=lambda t: -t[1])[:top]
        by_label: Dict[str, float] = defaultdict(float)
        for label, s in self.gaps():
            by_label[label] += s
        idle = sorted(by_label.items(), key=lambda t: -t[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in idle]}


def op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%l2_topk_pallas.1 = (f32[64,128]...) custom-call(...)``); keep the
    instruction's name."""
    return text.split(" = ", 1)[0]


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    window = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW} annotation in the trace")
    return Trace(devices=devices, spans=spans, window=window)
