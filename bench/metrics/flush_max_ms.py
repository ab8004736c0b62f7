"""flush_max_ms: wall time of the window's longest flush span (one
``store.search`` call, one scheduler micro-batch), the stall that sets a
closed loop's tail."""


def read(run):
    flushes = run.flushes()
    return max(f.seconds for f in flushes) * 1e3 if flushes else None
