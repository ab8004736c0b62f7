"""clauses_per_flush: per flush, the distinct ``where`` clauses whose host
pass mask the program built: its ``clauses`` counter (on
``search.predicate``), summed over the flush's ``serve.flush`` root; the
median over the window's flushes."""
from bench.harness import progspans


def read(run):
    return progspans.median_per_flush(run, "clauses", 1.0, counter=True)
