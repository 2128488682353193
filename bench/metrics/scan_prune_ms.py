"""Host milliseconds a query's file scans spend deciding, from the
footer's chunk statistics, which of their row groups no row passes: the
program's ``scan_prune`` span, one an entry of a scan with a pushed-down
predicate, on the decode thread beside ``scan_open``.  What pruning
costs, against the fetch and decode it saves.  Nothing where the
program has no such span (the parent) or pushes nothing down."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "scan_prune_ns", 1e-6)
