"""launches_per_flush: per flush, the kernel launches the program made: its
``launches`` counter (on ``l2_topk.prep``), summed over the flush's
``serve.flush`` root; the median over the window's flushes."""
from bench.harness import progspans


def read(run):
    return progspans.median_per_flush(run, "launches", 1.0, counter=True)
