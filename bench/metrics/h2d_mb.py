"""h2d_mb: per flush, the megabytes (1e6 bytes) the kernel wrapper sent
from host to device: the program's ``h2d_bytes`` counter, summed over the
flush; the median over the window's flushes."""
from bench.harness import progspans


def read(run):
    return progspans.median_per_flush(run, "h2d_bytes", 1e-6, counter=True)
