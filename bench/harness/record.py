"""What one run leaves for the metric readers (``bench/metrics/*.py``)."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from .spans import FLUSH, LAUNCH, Span
from .trace import Trace
from .traffic import Window


@dataclasses.dataclass
class RunRecord:
    cell: str
    config: Dict
    traffic: Dict
    device_kind: str
    setup_s: float
    window: Window
    spans: List[Span]              # recorded inside the measured window
    queue_ms: List[float]          # the scheduler's ServeStats.queue_ms
    storage_amp: float             # stored vectors / user vectors
    trace: Optional[Trace] = None  # only in a --trace 1 run

    def flushes(self) -> List[Span]:
        return [s for s in self.spans if s.name == FLUSH]

    def launches(self) -> List[Span]:
        return [s for s in self.spans if s.name == LAUNCH]
