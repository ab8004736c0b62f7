"""readback_ms: per flush, the summed wall time of the program's
``scan.readback`` spans (the host waiting for each launch's kernel, the
copies still in flight before it, and the copy back, then the id remap);
the median over the window's flushes."""
from bench.harness import progspans


def read(run):
    return progspans.median_per_flush(run, "scan.readback", 1e3)
