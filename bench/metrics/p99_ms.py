"""p99_ms: 99th percentile of the latency of every request sent in the
window, from when it was sent to when its answer reached the client, by
the host clock."""
import numpy as np


def read(run):
    lat = run.window.latencies_ms()
    return float(np.percentile(lat, 99)) if len(lat) else None
