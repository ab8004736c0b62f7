"""prep_ms: per flush, the summed wall time of the program's
``l2_topk.prep`` spans (the kernel wrapper from entry to the kernel call:
mask normalising, casts, and issuing the host-to-device copies, pads and
casts, which run asynchronously); the median over the window's
flushes."""
from bench.harness import progspans


def read(run):
    return progspans.median_per_flush(run, "l2_topk.prep", 1e3)
