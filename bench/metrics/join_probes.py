"""Probe batches a query sends through its joins: the openings of the
program's ``join_probe`` span.  Each is two device round trips, so the
count is the plan's (joins x non-empty batches reaching each), and a
change to it is a change of plan or of batching."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "join_probe_n", 1)
