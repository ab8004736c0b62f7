"""queue_ms: median wait of a window request in the scheduler's queue,
from submit to the cut of its micro-batch (the program's
``ServeStats.queue_ms``)."""
import numpy as np


def read(run):
    return float(np.median(run.queue_ms)) if run.queue_ms else None
