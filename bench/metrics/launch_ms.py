"""launch_ms: mean wall time of a launch span (one engine's
``search_masked_batch``: host preparation, copies to the device, the
kernel, the read-back)."""
import numpy as np


def read(run):
    ls = run.launches()
    return float(np.mean([s.seconds for s in ls])) * 1e3 if ls else None
