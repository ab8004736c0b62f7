"""pred_mask_ms: per flush, the summed wall time of the program's
``search.predicate`` spans (the batch's require/forbid rows and the host
pass mask of each distinct clause over every row); the median over the
window's flushes."""
from bench.harness import progspans


def read(run):
    return progspans.median_per_flush(run, "search.predicate", 1e3)
