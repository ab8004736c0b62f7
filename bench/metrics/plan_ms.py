"""plan_ms: per flush, the wall time of the program's ``search.plan`` span
(batch set-up, plan covers, authorized masks, purity waves); the median
over the window's flushes."""
from bench.harness import progspans


def read(run):
    return progspans.median_per_flush(run, "search.plan", 1e3)
