"""l2topk_roofline: the least time the chip could take for the work of the
traced window's ``l2_topk`` launches, over the device time of the kernel's
events in the trace, in percent.  The work of a launch is
``harness/work.py``'s; the peaks are ``peaks.json``'s for the device."""
import re

from bench.harness import work

# the kernel's instruction in the trace's XLA Ops line: %l2_topk_pallas.<n>
KERNEL = re.compile(r"^%?l2_topk_pallas\b")


def read(run):
    t = run.trace
    if t is None:
        return None
    kernel_s = t.op_seconds(KERNEL)
    launches = run.launches()
    if kernel_s <= 0 or not launches:
        return None
    peak = work.peaks(run.device_kind)
    least = sum(work.least_seconds(s, peak) for s in launches)
    share = 100.0 * least / kernel_s
    if share > 100.0:
        raise ValueError(
            f"l2topk_roofline {share:.3f}% > 100%: the work is counted too "
            f"high or the kernel's events leave out part of its time")
    return share
