"""engine_host_ms: per flush, the flush span's wall time less that of the
launch spans inside it (plan, waves, merges, host leftover scans); the
median over the window's flushes."""
import numpy as np


def read(run):
    launches = run.launches()
    own = []
    for f in run.flushes():
        inner = sum(s.seconds for s in launches
                    if s.t0 >= f.t0 and s.t1 <= f.t1)
        own.append(f.seconds - inner)
    return float(np.median(own)) * 1e3 if own else None
