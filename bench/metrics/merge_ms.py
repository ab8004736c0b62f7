"""merge_ms: per flush, the summed wall time of the program's
``search.merge`` spans (post-filters and top-k merges of each launch's
results, and the final answers); the median over the window's flushes."""
from bench.harness import progspans


def read(run):
    return progspans.median_per_flush(run, "search.merge", 1e3)
