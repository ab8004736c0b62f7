"""auth_mask_ms: per flush, the summed wall time of the program's
``search.authmask`` spans (the exact authorized mask of each distinct role
set of the batch); the median over the window's flushes."""
from bench.harness import progspans


def read(run):
    return progspans.median_per_flush(run, "search.authmask", 1e3)
