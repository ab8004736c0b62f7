"""idle_prep_pct: share of the traced window in which the first device
that ran anything runs no operation while a program ``l2_topk.prep`` span
is open, the program's spans mapped onto the trace clock
(``harness/progspans.py``); of the part of the window whose spans the
program still holds, where it let the first ones go."""
from bench.harness import progspans


def read(run):
    got = progspans.idle_under(run, "l2_topk.prep")
    return None if got is None else 100.0 * got[0] / got[1]
